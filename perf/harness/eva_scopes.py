"""Device seconds of a cell's programs under the EVA block's named scopes
(``attn.eva``: scores over ring and summaries, the one softmax, the context;
``eva.pool``: the chunk summaries; beside them ``attn.proj``, ``ffn.dense``
and ``head`` for the printed breakdown), read once a run and a program
pattern from the raw trace through ``xplane_scopes.scope_seconds`` and kept in
``obs`` for the metrics that share it (``eva_share_of_step``,
``eva_share_of_prefill``, ``eva_roofline``, ``step_roofline.serve_eva``).  A
run without a trace, or a program without the scopes (the parent of the PR
that added them), gives ``None``."""
import json
import os

from . import stats, trace_reduce, xplane_scopes

EVA = ("attn.eva", "eva.pool")
SCOPES = EVA + ("attn.proj", "ffn.dense", "head")


def seconds(obs, module):
    """``{"eva": s, "programs": s}``: the device seconds under ``attn.eva``
    and ``eva.pool`` and of the programs whose name matches ``module``, or
    ``None``."""
    key = "eva_scopes:" + module
    if key not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = xplane_scopes.scope_seconds(
                trace_reduce.find_xplane(root), module, SCOPES)
        if found is not None:
            print(f"by_scope.eva {module} " + json.dumps(
                {s: round(found[s], 6) for s in SCOPES + ("_programs",)}),
                flush=True)
        obs[key] = found
    found = obs[key]
    if not found or not found["_programs"] or \
            not any(found[s] for s in EVA):
        return None
    return {"eva": sum(found[s] for s in EVA), "programs": found["_programs"]}


def share(obs, spec):
    """Percent of the device seconds of the programs ``spec["module"]``
    under EVA's scopes, or ``None``."""
    found = seconds(obs, spec["module"])
    return None if found is None else 100.0 * found["eva"] / found["programs"]


def step_load(obs):
    """``(live rows, live ring entries, live summary rows)`` of the mean
    step, from the flight recorder and the program's ``decode.eva.*``
    counters, or ``None`` where the program counts none."""
    c = obs.get("counters") or {}
    rows = [v for _t, name, _d, v in obs.get("flight") or []
            if name == "decode.step" and v]
    layer_steps = c.get("decode.eva.layer_steps")
    if not rows or not layer_steps:
        return None
    return (stats.mean(rows), c.get("decode.eva.ring_rows", 0) / layer_steps,
            c.get("decode.eva.summary_rows", 0) / layer_steps)
