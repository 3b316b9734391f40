"""The CPU a launch costs the scheduler's thread: mean ``cpu_ms`` of the
``decode.step.dispatch`` spans that start outside the profiler's seconds (a
mean, since a host's CPU clock may tick in milliseconds and only sums are
unbiased; ``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    return handover.cpu_mean_ms(obs, spec["span"])
