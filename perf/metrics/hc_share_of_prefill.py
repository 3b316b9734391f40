"""Share of the prefill programs' device time (the hybridized block's
forward, ``jit_pure``: one prompt a call) spent in the operations under the
named scopes ``hc.coef``, ``hc.sinkhorn`` and ``hc.mix``: every sublayer
reads each token's four streams for the coefficients, again for the read,
and reads and writes them for the write-back.  Read from the raw trace
(``perf/harness/hc_scopes.py``)."""
from perf.harness.hc_scopes import share as read  # noqa: F401
