"""Device seconds of the step programs under the window / global block's
own attention scopes (``attn.window``: a window layer's projections, the
write into the ring, the ring's read, scores with the sink, the output
product; ``attn.global``: the same over the row's gathered pages), read
once a run from the raw trace through ``xplane_scopes.scope_seconds`` and
kept in ``obs`` for the two metrics that share it
(``window_attention_share_of_step``, ``global_attention_share_of_step``).
A run without a trace, or a program without these scopes, gives ``None``."""
import json
import os

from . import trace_reduce, xplane_scopes

SCOPES = ("attn.window", "attn.global")


def seconds(obs, step_module):
    """``{"attn.window": s, "attn.global": s, "programs": s}`` or
    ``None``."""
    key = "window_scopes:" + step_module
    if key not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = xplane_scopes.scope_seconds(
                trace_reduce.find_xplane(root), step_module, SCOPES)
        if found is not None:
            print("step_by_scope.window " + json.dumps(
                {s: round(found[s], 6) for s in SCOPES + ("_programs",)}),
                flush=True)
        obs[key] = found
    found = obs[key]
    if not found or not found["_programs"] or \
            not any(found[s] for s in SCOPES):
        return None
    return dict({s: found[s] for s in SCOPES}, programs=found["_programs"])


def share(obs, spec):
    """Percent of the step programs' device seconds under ``spec["scope"]``,
    or ``None``."""
    found = seconds(obs, spec["step_module"])
    return None if found is None \
        else 100.0 * found[spec["scope"]] / found["programs"]
