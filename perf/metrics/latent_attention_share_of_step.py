"""Share of the step programs' device time spent in the operations under
the named scope ``mla.attend`` (query and latent projections, the page
write, the paged gather, absorbed scores and context, the output
projection), read from the raw trace (``perf/harness/xplane_scopes.py``)."""
from perf.harness import xplane_scopes


def read(obs, spec):
    return xplane_scopes.share_of_programs(obs, spec)
