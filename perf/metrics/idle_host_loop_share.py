"""Share of the traced window in which the first device runs nothing while
the scheduler's thread is inside ``decode.boundary`` and outside a ``.fetch``
span: idle time that is the host loop's (``perf/harness/idle_phases.py``)."""
from perf.harness import idle_phases


def read(obs, spec):
    return idle_phases.host_loop_share(obs)
