"""Device seconds of a cell's programs under the hyper-connections' named
scopes (``hc.coef``: the flattened stream's norm, the projection onto the 24
coefficients, the sigmoids; ``hc.sinkhorn``: the 20 normalisation rounds;
``hc.mix``: the read ``Hpre X`` and the write-back ``Hres X + Hpost^T y``),
read once a run and a program pattern from the raw trace through
``xplane_scopes.scope_seconds`` and kept in ``obs`` for the metrics that
share it (``hc_share_of_step``, ``hc_roofline``, ``hc_share_of_prefill``).
A run without a trace, or a program without the scopes (the parent of the PR
that added them), gives ``None``."""
import json
import os

from . import trace_reduce, xplane_scopes

HC = ("hc.coef", "hc.sinkhorn", "hc.mix")
SCOPES = HC + xplane_scopes.ALL_SCOPES


def seconds(obs, module):
    """``{"hc": s, "programs": s}``: the device seconds under ``hc.*`` and
    of the programs whose name matches ``module``, or ``None``."""
    key = "hc_scopes:" + module
    if key not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = xplane_scopes.scope_seconds(
                trace_reduce.find_xplane(root), module, SCOPES)
        if found is not None:
            print(f"by_scope.hc {module} " + json.dumps(
                {s: round(found[s], 6) for s in SCOPES + ("_programs",)}),
                flush=True)
        obs[key] = found
    found = obs[key]
    if not found or not found["_programs"] or \
            not any(found[s] for s in HC):
        return None
    return {"hc": sum(found[s] for s in HC), "programs": found["_programs"]}


def share(obs, spec):
    """Percent of the device seconds of the programs ``spec["module"]``
    under ``hc.*``, or ``None``."""
    found = seconds(obs, spec["module"])
    return None if found is None else 100.0 * found["hc"] / found["programs"]
