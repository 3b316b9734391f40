"""Share of the step programs' device time spent in the operations under
the named scope ``attn.gqa`` (the grouped-query attention layers: their
projections, the gather of each row's reserved pages, scores, softmax and
the output product), read from the raw trace
(``perf/harness/ssm_scopes.py``)."""
from perf.harness import ssm_scopes


def read(obs, spec):
    found = ssm_scopes.seconds(obs, spec["step_module"])
    return None if found is None \
        else 100.0 * found["attn"] / found["programs"]
