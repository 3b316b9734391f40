"""Share of the step programs' device time spent in the operations under
the named scope ``attn.global`` (the global grouped-query layers:
projections, rotary, the page write, the gather of each row's reserved
pages, scores, softmax and the output product), read from the raw trace
(``perf/harness/window_scopes.py``)."""
from perf.harness import window_scopes


def read(obs, spec):
    return window_scopes.share(obs, spec)
