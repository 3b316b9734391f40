"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's trick of one op suite re-run per backend
(tests/python/gpu/test_operator_gpu.py:37-45 does set_default_context +
re-import): here the suite runs on CPU with 8 virtual devices so that all
sharding/collective paths compile and execute without TPU hardware.  The
chip is reached only by ``python chip_smoke.py`` through the chip tool.
"""
import contextlib
import os
import signal
import tempfile
import threading

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Where the suite's compile cache lives (turned on below), unless it is placed
# from outside: a fixed name under the system's temporary directory, because a
# whole run leaves 14,000 entries and 120 MB, too much to keep in a checkout
# that is copied.  Through the environment, so that the children the tests
# start share it, every program kept however quickly it compiled.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "mxnet_tpu_suite_jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

# setdefault leaves a JAX_PLATFORMS that names the chip in place (the chip
# machine's own is "tpu,cpu") — force CPU through the config API too, so
# the suite never claims the TPU.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from mxnet_tpu.runtime import compile_cache  # noqa: E402

# A program is compiled once: every process of the suite (the six workers,
# and the children they start) shares the program's own persistent compile
# cache.  More than half of the suite's seconds were compiles, the same
# seeding, broadcasting and elementwise programs once a process.  A module
# whose programs must come from the compiler takes ``compiled_anew`` below.
compile_cache()


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



#: Seconds one test may take from its set-up to its teardown: two and a half
#: times the longest test there is (the twelve-layer BERT step compiled for a
#: described v5e, 244 s in a whole run), well inside the run's 1,470 s.
TEST_LIMIT_S = 600.0


@contextlib.contextmanager
def time_limit(seconds, name):
    """Fail ``name`` when the body runs past ``seconds``.  The alarm is the
    process's one real-time timer and is delivered to the main thread, so
    elsewhere, and where there is no ``SIGALRM``, this does nothing; the
    handler and the timer that were there come back on the way out."""
    if not hasattr(signal, "SIGALRM") or \
            threading.current_thread() is not threading.main_thread():
        yield
        return

    def fired(_signum, _frame):
        pytest.fail(f"{name} ran past its limit of {seconds:g} s "
                    f"(tests/conftest.py)", pytrace=False)

    handler = signal.signal(signal.SIGALRM, fired)
    timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *timer)
        signal.signal(signal.SIGALRM, handler)


@pytest.fixture(autouse=True)
def _limit(request):
    """A limit of its own for every test (``pytest-timeout`` is not
    installed): a test that hangs fails by name and costs itself, not the
    run.  (Fixtures of a wider scope are set up before it, outside.)"""
    with time_limit(TEST_LIMIT_S, request.node.nodeid):
        yield


@pytest.fixture(scope="module")
def compiled_anew():
    """The suite's persistent compilation cache off around a module whose
    programs have to come from the compiler: a compile for a described chip
    (``one_chip``) is written to the cache but cannot be read back without
    one, and would warn; and on the CPU an executable that the cache LOADED
    serializes without its kernels, so a ``serving.aot.ProgramCache`` entry
    stored from one fails where it is loaded (``tests/test_aot_cache.py``;
    ``ROADMAP.md`` D22)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(compiled_anew):
    """``SingleDeviceSharding`` on the first device of a described v5e 2x2
    host (``tests/test_chip_compile*.py``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler: skip
        pytest.skip(f"cannot describe a v5e topology here: {e!r}"[:300])
    return SingleDeviceSharding(topo.devices[0])
