"""Roofline share of the Mamba-2 mixers of the decode step, the one new
piece of device code of the hybrid block (XLA's fusions today; a kernel
would report here too): the least time the mixers of one step of the mean
batch could take (their weights once and the LIVE rows' state read and
written once a layer over the chip's bandwidth, against their FLOPs over
the peak, the larger; ``perf/harness/flops_hybrid_moe.py``) over the device
seconds a step spends under the scopes ``ssm.mix`` + ``ssm.conv``."""
from perf.harness import flops, flops_hybrid_moe, ssm_scopes, stats


def read(obs, spec):
    tr = obs.get("trace")
    rows = [v for _t, name, _d, v in obs.get("flight") or []
            if name == "decode.step" and v]
    found = ssm_scopes.seconds(obs, spec["step_module"])
    if tr is None or not rows or found is None:
        return None
    n, _total = tr.module_seconds(spec["step_module"])
    if not n:
        return None
    cost = flops_hybrid_moe.ssm_step_cost(obs["cell"].config,
                                          stats.mean(rows))
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"ssm_roofline: {bound}-bound, least {least * 1e3:.3f} ms "
          f"({cost['weight_bytes'] / 1e9:.3f} GB of weights, "
          f"{cost['state_bytes'] / 1e9:.3f} GB of live state at "
          f"{stats.mean(rows):.2f} rows), device "
          f"{found['ssm'] / n * 1e3:.3f} ms a step under ssm.mix + ssm.conv",
          flush=True)
    return 100.0 * least / (found["ssm"] / n)
