"""Share of the step programs' device time spent in the operations under
the named scopes ``kda.mix``, ``kda.conv`` and ``kda.recur`` (the KDA
mixers: their projections and gates, the three convolutions with their
tails' read and write, the recurrence on the slot's matrix state, the gated
norm and the output projection), read from the raw trace
(``perf/harness/kda_scopes.py``)."""
from perf.harness.kda_scopes import share as read  # noqa: F401
