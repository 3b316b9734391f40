"""Share of the step programs' device time spent in the operations under
the named scopes ``moe.experts``, ``moe.shared`` and ``moe.route`` (the
routed experts' grouped products, the shared expert, the router and the
sort), read from the raw trace (``perf/harness/xplane_scopes.py``)."""
from perf.harness import xplane_scopes


def read(obs, spec):
    return xplane_scopes.share_of_programs(obs, spec)
