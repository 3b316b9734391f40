"""Roofline share of the training step: model FLOPs of forward plus
backward (no recomputation counted) over the chips' bf16 peak, over the step
program's mean device time in the trace."""
from perf.harness import flops


def read(obs, spec):
    tr = obs.get("trace")
    if tr is None:
        return None
    n, total = tr.module_seconds(spec["step_module"])
    if not n:
        return None
    cell = obs["cell"]
    rows = cell.traffic["per_chip_batch"] * obs["chips"]
    cost = {"flops": flops.bert_step_flops(cell.config, rows,
                                           cell.traffic["seq_len"])}
    least, _bound = flops.least_seconds(cost, obs["peaks"], obs["chips"])
    return 100.0 * least / (total / n)
