"""Share of the step programs' device time spent in the operations under
the named scopes ``ssm.mix`` and ``ssm.conv`` (the Mamba-2 mixers: their
projections, the convolution, the recurrence on the slot's state with its
read and write, the gated norm), read from the raw trace
(``perf/harness/ssm_scopes.py``)."""
from perf.harness import ssm_scopes


def read(obs, spec):
    found = ssm_scopes.seconds(obs, spec["step_module"])
    return None if found is None \
        else 100.0 * found["ssm"] / found["programs"]
