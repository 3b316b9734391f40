"""Device seconds of the step programs under the linear-attention block's
own named scopes (``kda.mix``: a KDA mixer's projections, gates and gated
norm; ``kda.conv``: the three convolutions and their tails; ``kda.recur``:
the recurrence on the slot's matrix state; ``attn.gqa``: the grouped-query
layers), read once a run from the raw trace through
``xplane_scopes.scope_seconds`` and kept in ``obs`` for the metrics that
share it (``kda_share_of_step``, ``kda_roofline``,
``gated_attention_share_of_step``).  A run without a trace, or a program
without the KDA scopes (the parent of the PR that added them), gives
``None``."""
import json
import os

from . import trace_reduce, xplane_scopes

KDA = ("kda.mix", "kda.conv", "kda.recur")
SCOPES = KDA + ("attn.gqa",)


def seconds(obs, step_module):
    """``{"kda": s, "attn": s, "programs": s}`` or ``None``."""
    key = "kda_scopes:" + step_module
    if key not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = xplane_scopes.scope_seconds(
                trace_reduce.find_xplane(root), step_module, SCOPES)
        if found is not None:
            print("step_by_scope.linear " + json.dumps(
                {s: round(found[s], 6) for s in SCOPES + ("_programs",)}),
                flush=True)
        obs[key] = found
    found = obs[key]
    if not found or not found["_programs"] or \
            not any(found[s] for s in KDA):
        return None
    return {"kda": sum(found[s] for s in KDA), "attn": found["attn.gqa"],
            "programs": found["_programs"]}


def share(obs, spec):
    """Percent of the step programs' device seconds under ``spec["under"]``
    (``"kda"`` or ``"attn"``), or ``None``."""
    found = seconds(obs, spec["step_module"])
    return None if found is None \
        else 100.0 * found[spec["under"]] / found["programs"]
