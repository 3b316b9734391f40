"""Roofline share of the hyper-connections of the decode step (XLA's
fusions under ``hc.coef``, ``hc.sinkhorn`` and ``hc.mix``; no kernel of the
repo's own yet): the least time the 80 sublayers' mixing of one step of the
mean batch could take (each ``phi`` once in float32 and each LIVE row's
stream read three times and written once a sublayer over the chip's
bandwidth, against their FLOPs over the peak, the larger;
``perf/harness/flops_hyper_latent_moe.py``) over the device seconds a step
spends under those scopes."""
from perf.harness import flops, flops_hyper_latent_moe, hc_scopes, stats


def read(obs, spec):
    tr = obs.get("trace")
    rows = [v for _t, name, _d, v in obs.get("flight") or []
            if name == "decode.step" and v]
    found = hc_scopes.seconds(obs, spec["module"])
    if tr is None or not rows or found is None:
        return None
    n, _total = tr.module_seconds(spec["module"])
    if not n:
        return None
    cost = flops_hyper_latent_moe.hc_step_cost(obs["cell"].config,
                                               stats.mean(rows))
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"hc_roofline: {bound}-bound, least {least * 1e3:.3f} ms "
          f"({cost['param_bytes'] / 1e9:.3f} GB of phi, "
          f"{cost['stream_bytes'] / 1e9:.4f} GB of streams at "
          f"{stats.mean(rows):.2f} rows), device "
          f"{found['hc'] / n * 1e3:.3f} ms a step under hc.*", flush=True)
    return 100.0 * least / (found["hc"] / n)
