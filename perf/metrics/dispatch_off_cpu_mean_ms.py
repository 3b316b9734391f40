"""The time the launching thread stood off the CPU inside a launch: mean
``dur - cpu_ms`` of the ``decode.step.dispatch`` spans that start outside the
profiler's seconds.  Nothing in a ``.dispatch`` waits for the device, so this
is the runtime's own blocking (a transfer, the enqueue) PLUS the wait for the
interpreter's lock; a cell whose interpreter is free (one row, no writers)
reads the first alone, and the lock's part elsewhere is what lies above that
floor (``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    return handover.cpu_mean_ms(obs, spec["span"], off_cpu=True)
