"""Share of the step programs' device time spent in the operations under
the named scope ``attn.window`` (the sliding-window layers: projections,
rotary, the token's write into the slot's ring, the ring's read, scores and
softmax with the sink, the output product), read from the raw trace
(``perf/harness/window_scopes.py``)."""
from perf.harness import window_scopes


def read(obs, spec):
    return window_scopes.share(obs, spec)
