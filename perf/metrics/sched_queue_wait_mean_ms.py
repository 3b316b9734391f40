"""Mean of the scheduler's ``decode.queue_wait`` spans: ``submit()`` to the
start of the request's prefill, the wait that is a share of
``ttft_mean_ms``."""
from perf.harness import idle_phases


def read(obs, spec):
    return idle_phases.span_mean_ms(obs, spec["span"])
