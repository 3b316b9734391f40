"""Roofline share of EVA's own part of the decode step (XLA's fusions under
``attn.eva`` and ``eva.pool``; no kernel of the repo's own yet): the least
time the 16 layers' LIVE ring entries and LIVE summary rows of the mean step
could take to read once, with the new entries and summaries written (over the
chip's bandwidth, against their FLOPs over the peak, the larger;
``perf/harness/flops_eva.py``), over the device seconds a step spends under
those scopes."""
from perf.harness import eva_scopes, flops, flops_eva


def read(obs, spec):
    tr = obs.get("trace")
    load = eva_scopes.step_load(obs)
    found = eva_scopes.seconds(obs, spec["module"])
    if tr is None or load is None or found is None:
        return None
    n, _total = tr.module_seconds(spec["module"])
    if not n:
        return None
    rows, ring, summaries = load
    cost = flops_eva.eva_step_cost(obs["cell"].config, rows, ring, summaries)
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"eva_roofline: {bound}-bound, least {least * 1e3:.3f} ms "
          f"({cost['ring_bytes'] / 1e9:.3f} GB of live ring entries, "
          f"{cost['summary_bytes'] / 1e9:.3f} GB of live summary rows at "
          f"{rows:.2f} rows), device {found['eva'] / n * 1e3:.3f} ms a step "
          f"under attn.eva and eva.pool", flush=True)
    return 100.0 * least / (found["eva"] / n)
