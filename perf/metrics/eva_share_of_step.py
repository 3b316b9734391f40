"""Share of the step programs' device time spent in the operations under the
named scopes ``attn.eva`` (scores over the slot's ring and the gathered
summaries, the one softmax, the context) and ``eva.pool`` (the chunk's
summary), read from the raw trace (``perf/harness/eva_scopes.py``).  Prints
the program's last ``decode.eva.*`` gauges beside it."""
from perf.harness import eva_scopes


def read(obs, spec):
    share = eva_scopes.share(obs, spec)
    if share is not None:
        from mxnet_tpu.telemetry import bus
        gauges = {k: v for k, v in bus.snapshot()["gauges"].items()
                  if k.startswith("decode.eva.")}
        print(f"eva_share_of_step: gauges {gauges}", flush=True)
    return share
