"""Share of the prefill programs' device time (the hybridized block's forward,
``jit_pure``: one prompt a call) spent in the operations under the named
scopes ``attn.eva`` (a block of queries over its window's keys and the closed
windows' summaries) and ``eva.pool`` (every chunk's summary).  Read from the
raw trace (``perf/harness/eva_scopes.py``)."""
from perf.harness.eva_scopes import share as read  # noqa: F401
