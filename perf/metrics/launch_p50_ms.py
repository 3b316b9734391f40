"""Median over the traced ``decode.step`` calls of the time from the
``.dispatch`` span's opening to the program's first operation on the device,
the device's line moved to the middle of the bracket that causality leaves
(``perf/harness/handover.py``, which prints the bracket)."""
from perf.harness import handover


def read(obs, spec):
    return handover.step_p50_ms(obs, "launch_s")
