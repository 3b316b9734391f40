"""CPU seconds every OTHER thread of the process burned while the loop was
at work, a second of the loop's: sum of ``proc_cpu_ms - cpu_ms`` over sum of
durations of the ``decode.boundary`` spans that start outside the profiler's
seconds.  The printed ``cpu_cores`` line has the door's part beside it
(``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    found = handover.loop_cpu(obs, spec["span"])
    return found and found["other_cores"]
