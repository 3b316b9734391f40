"""Roofline share of the EVA-attention byte-level decode step: the least time
one step of the mean batch could take (every weight once, the LIVE ring
entries and the LIVE summary rows of the live sequences, the new entries and
summaries written, over the chip's bandwidth, against its FLOPs over the peak,
the larger; ``perf/harness/flops_eva.py``) over the step programs' mean device
time in the trace.  Prints which bound it used.  The count is of live bytes
whatever the program reads: what the 8-row program moves for padded rows, for
ring entries past ``position mod window`` and for reserved summary rows that
no closed window has filled is in the time and not in the least time.  The
live entries and rows are the program's own counters (``decode.eva.*``); a
program without them, or without EVA's scopes, gives nothing."""
from perf.harness import eva_scopes, flops, flops_eva


def read(obs, spec):
    tr = obs.get("trace")
    load = eva_scopes.step_load(obs)
    if tr is None or load is None or \
            eva_scopes.seconds(obs, spec["module"]) is None:
        return None
    n, total = tr.module_seconds(spec["module"])
    if not n:
        return None
    rows, ring, summaries = load
    cost = flops_eva.decode_step_cost(obs["cell"].config, rows, ring,
                                      summaries)
    least, bound = flops.least_seconds(cost, obs["peaks"])
    print(f"step_roofline.serve_eva: {bound}-bound, least {least * 1e3:.3f} "
          f"ms ({cost['always_read_bytes'] / 1e9:.3f} GB of weights, "
          f"{cost['ring_bytes'] / 1e9:.3f} GB of {ring:.0f} live ring "
          f"entries, {cost['summary_bytes'] / 1e9:.3f} GB of {summaries:.0f} "
          f"live summary rows), device {total / n * 1e3:.3f} ms a step at "
          f"{rows:.2f} rows", flush=True)
    return 100.0 * least / (total / n)
