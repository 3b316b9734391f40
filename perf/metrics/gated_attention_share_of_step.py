"""Share of the step programs' device time spent in the operations under
the named scope ``attn.gqa`` of the linear-attention block (the gated
grouped-query layers: their projections and gate, the new row's write, the
paged attention over each row's live pages and the output product), read
from the raw trace (``perf/harness/kda_scopes.py``)."""
from perf.harness.kda_scopes import share as read  # noqa: F401
