"""Roofline share of the decode step: the least time one step of the mean
batch could take (weights and live KV read once over the chip's bandwidth,
against its FLOPs over the peak, the larger) over the step program's mean
device time in the trace."""
from perf.harness import flops, stats


def read(obs, spec):
    tr = obs.get("trace")
    rows = [v for _t, name, _d, v in obs.get("flight") or []
            if name == "decode.step" and v]
    live = (obs.get("samples") or {}).get("live_tokens_per_row")
    if tr is None or not rows or not live:
        return None
    n, total = tr.module_seconds(spec["step_module"])
    if not n:
        return None
    cost = flops.decode_step_cost(obs["cell"].config, stats.mean(rows),
                                  stats.mean(live), spec["weight_bytes"],
                                  spec["kv_bytes"])
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"step_roofline.serve: {bound}-bound, least {least * 1e3:.3f} ms, "
          f"device {total / n * 1e3:.3f} ms a step at {stats.mean(rows):.2f} "
          f"rows", flush=True)
    return 100.0 * least / (total / n)
