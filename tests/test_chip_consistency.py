"""CPU <-> TPU-chip operator consistency (the reference's
``check_consistency``/one-suite-per-backend strategy,
``tests/python/gpu/test_operator_gpu.py:37-45``).

The comparison itself needs the chip, so it is a phase of the chip smoke:
``python chip_smoke.py --ops`` runs the curated batch
(``chip_consistency_worker.op_batch``) and the generated registry sweep
(``chip_consistency_sweep.sweep_batch``) on the chip in one child process
and on the CPU in another, and compares them at fp32 tolerances (both sides
under ``default_matmul_precision('highest')``).  What runs here, on the
CPU, guards the sweep's coverage.
"""
import mxnet_tpu as mx
from chip_consistency_sweep import sweep_batch


def test_sweep_coverage_floor():
    """The generated sweep must cover ≥250 registered ops on this build —
    a silent synthesis regression would otherwise hollow out the
    chip-consistency guarantee (reference runs its whole operator suite
    on the second backend)."""
    skips = {}
    out = sweep_batch(mx, mx.cpu(), collect_skips=skips)
    assert len(out) >= 250, (len(out), sorted(
        k for k, v in skips.items() if "synthesis failed" in v)[:30])


def test_curated_batch_runs_on_cpu():
    """The CPU half of ``chip_smoke.py --ops``'s curated batch: every op
    of the list runs and is finite on this backend."""
    import numpy as np
    from chip_consistency_worker import op_batch
    out = op_batch(mx, mx.cpu())
    assert len(out) >= 29
    for name, arr in out.items():
        assert np.isfinite(arr.asnumpy()).all(), name
