"""Roofline share of the hyper-connected latent-attention, routed-expert
decode step: the least time one step of the mean batch could take (the
always-read weights and every sublayer's ``phi``, each held expert that
received a row, the live latent rows, and each live row's four streams read
three times and written once a sublayer, over the chip's bandwidth, against
its FLOPs over the peak, the larger) over the step programs' mean device
time in the trace.  Prints which bound it used.  The experts hit and the
held assignments are the program's own counters (``decode.moe.*``); a
program without them, or without the hyper-connections' scopes, gives
nothing."""
from perf.harness import (flops, flops_hyper_latent_moe, hc_scopes,
                          stats)


def read(obs, spec):
    tr = obs.get("trace")
    c = obs.get("counters") or {}
    rows = [v for _t, name, _d, v in obs.get("flight") or []
            if name == "decode.step" and v]
    live = (obs.get("samples") or {}).get("live_tokens_per_row")
    layer_steps = c.get("decode.moe.layer_steps")
    if tr is None or not rows or not live or not layer_steps \
            or not c.get("decode.steps") \
            or hc_scopes.seconds(obs, spec["module"]) is None:
        return None
    n, total = tr.module_seconds(spec["module"])
    if not n:
        return None
    hit = c.get("decode.moe.experts_hit", 0) / layer_steps
    held = c.get("decode.moe.assignments_held", 0) / c["decode.steps"]
    cost = flops_hyper_latent_moe.decode_step_cost(
        obs["cell"].config, stats.mean(rows), stats.mean(live), hit, held)
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"step_roofline.serve_hyper_moe: {bound}-bound, least "
          f"{least * 1e3:.3f} ms ({cost['always_read_bytes'] / 1e9:.3f} GB "
          f"always read, {hit:.2f} experts hit a layer, "
          f"{cost['hc_bytes'] / 1e9:.3f} GB of hyper-connections, "
          f"{stats.mean(live):.0f} live tokens a row), device "
          f"{total / n * 1e3:.3f} ms a step at {stats.mean(rows):.2f} rows",
          flush=True)
    return 100.0 * least / (total / n)
