"""Share of the prefill programs' device time (the hybridized block's
forward, ``jit_pure``: one prompt a call) spent in the operations under the
named scope ``mla.attend`` (query and latent projections, the per-head keys
and values out of ``W_kvb``, the prompt's causal attention, the output
projection), read from the raw trace (``perf/harness/xplane_scopes.py``)."""
from perf.harness import xplane_scopes


def read(obs, spec):
    return xplane_scopes.share_of_programs(obs, spec)
