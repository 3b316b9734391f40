"""Device seconds of the step programs under the hybrid block's own named
scopes (``ssm.mix``: projections, recurrence, gated norm; ``ssm.conv``;
``attn.gqa``), read once a run from the raw trace through
``xplane_scopes.scope_seconds`` and kept in ``obs`` for the metrics that
share it (``ssm_share_of_step``, ``ssm_roofline``,
``gqa_attention_share_of_step``).  A run without a trace, or a program
without the state-space scopes, gives ``None``."""
import json
import os

from . import trace_reduce, xplane_scopes

SCOPES = ("ssm.mix", "ssm.conv", "attn.gqa")


def seconds(obs, step_module):
    """``{"ssm": s, "attn": s, "programs": s}`` or ``None``."""
    key = "ssm_scopes:" + step_module
    if key not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = xplane_scopes.scope_seconds(
                trace_reduce.find_xplane(root), step_module, SCOPES)
        if found is not None:
            print("step_by_scope.hybrid " + json.dumps(
                {s: round(found[s], 6) for s in SCOPES + ("_programs",)}),
                flush=True)
        obs[key] = found
    found = obs[key]
    if not found or not found["_programs"] or \
            not (found["ssm.mix"] or found["ssm.conv"]):
        return None
    return {"ssm": found["ssm.mix"] + found["ssm.conv"],
            "attn": found["attn.gqa"], "programs": found["_programs"]}
