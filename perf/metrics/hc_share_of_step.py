"""Share of the step programs' device time spent in the operations under
the named scopes ``hc.coef``, ``hc.sinkhorn`` and ``hc.mix`` (the
hyper-connections of the residual path: 80 chains of a norm, a 24-column
product, 20 normalisation rounds on 4 x 4, a read and a write-back of the
four streams), read from the raw trace (``perf/harness/hc_scopes.py``).
Prints the program's last ``decode.hc.sinkhorn_residual`` gauge beside it:
how far the last step's worst ``Hres`` lay from doubly stochastic."""
from perf.harness import hc_scopes


def read(obs, spec):
    share = hc_scopes.share(obs, spec)
    if share is not None:
        from mxnet_tpu.telemetry import bus
        gauges = {k: v for k, v in bus.snapshot()["gauges"].items()
                  if k.startswith("decode.hc.")}
        print(f"hc_share_of_step: gauges {gauges}", flush=True)
    return share
