"""The scheduler thread's CPU seconds over its turns' seconds: sum of
``cpu_ms`` over sum of durations of the ``decode.boundary`` spans that start
outside the profiler's seconds (``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    found = handover.loop_cpu(obs, spec["span"])
    return found and found["share_pct"]
