"""Median over the traced ``decode.step`` calls of the time from the
program's last operation on the device to the ``.fetch`` span's end (the
thread running again with its tokens), at the middle of the bracket
(``perf/harness/handover.py``)."""
from perf.harness import handover


def read(obs, spec):
    return handover.step_p50_ms(obs, "wake_s")
