"""Mean of the gateway's ``gateway.first_frame`` spans: the scheduler
thread's first ``_put`` to the flush of the first SSE frame, the hand-over
between the two threads."""
from perf.harness import idle_phases


def read(obs, spec):
    return idle_phases.span_mean_ms(obs, spec["span"])
