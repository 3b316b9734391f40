"""Roofline share of the KDA mixers of the decode step, the new piece of
device code of the linear-attention block (the kernel ``kda_step_slots``
and XLA's fusions around it): the least time the mixers of one step of the
mean batch could take (their weights once and the LIVE rows' matrix state
and tails read and written once a layer over the chip's bandwidth, against
their FLOPs over the peak, the larger;
``perf/harness/flops_linear_moe.py``) over the device seconds a step spends
under the scopes ``kda.mix`` + ``kda.conv`` + ``kda.recur``."""
from perf.harness import flops, flops_linear_moe, kda_scopes, stats


def read(obs, spec):
    tr = obs.get("trace")
    rows = [v for _t, name, _d, v in obs.get("flight") or []
            if name == "decode.step" and v]
    found = kda_scopes.seconds(obs, spec["step_module"])
    if tr is None or not rows or found is None:
        return None
    n, _total = tr.module_seconds(spec["step_module"])
    if not n:
        return None
    cost = flops_linear_moe.kda_step_cost(obs["cell"].config,
                                          stats.mean(rows))
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"kda_roofline: {bound}-bound, least {least * 1e3:.3f} ms "
          f"({cost['weight_bytes'] / 1e9:.3f} GB of weights, "
          f"{cost['state_bytes'] / 1e9:.3f} GB of live state at "
          f"{stats.mean(rows):.2f} rows), device "
          f"{found['kda'] / n * 1e3:.3f} ms a step under kda.*",
          flush=True)
    return 100.0 * least / (found["kda"] / n)
