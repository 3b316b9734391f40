"""Median over the traced ``decode.step`` calls of the span (``.dispatch``'s
start to ``.fetch``'s end) minus its program's extent on the device's line:
exact whatever the two clocks' disagreement (``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    return handover.step_p50_ms(obs, "handover_s")
