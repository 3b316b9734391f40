"""What the profiler adds to every host number of a traced run: the median
``decode.step`` span that starts inside the profiler's seconds over the
median of those that start outside them, less 1
(``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    return handover.profiler_stretch(obs, spec["span"])
