"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's trick of one op suite re-run per backend
(tests/python/gpu/test_operator_gpu.py:37-45 does set_default_context +
re-import): here the suite runs on CPU with 8 virtual devices so that all
sharding/collective paths compile and execute without TPU hardware.  The
chip is reached only by ``python chip_smoke.py`` through the chip tool.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# setdefault leaves a JAX_PLATFORMS that names the chip in place (the chip
# machine's own is "tpu,cpu") — force CPU through the config API too, so
# the suite never claims the TPU.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    """Deterministic seeds per test (reference tests/python/unittest/common.py
    @with_seed): default 0, overridable via MXNET_TEST_SEED — the knob
    tools/flakiness_checker.py varies per trial."""
    import random as _pyrandom

    import mxnet_tpu as mx

    seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
    np.random.seed(seed)
    mx.random.seed(seed)
    _pyrandom.seed(seed)
    yield

