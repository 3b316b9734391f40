"""Share of the traced window in which the first device runs nothing while
the scheduler's thread waits inside ``decode.idle``: no request to serve
(``perf/harness/idle_phases.py``)."""
from perf.harness import idle_phases


def read(obs, spec):
    return idle_phases.no_work_share(obs)
