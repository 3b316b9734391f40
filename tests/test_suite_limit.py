"""The suite's own limit on a test, and the compile cache its processes
share (``tests/conftest.py``)."""
import glob
import json
import os
import signal
import subprocess
import sys
import time

import jax
import pytest

from conftest import TEST_LIMIT_S, time_limit


@pytest.mark.skipif(not hasattr(signal, "SIGALRM"), reason="no SIGALRM")
def test_a_body_past_its_limit_fails_by_name():
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"tests/x\.py::test_y ran past its limit of "
                             r"0\.2 s"):
        with time_limit(0.2, "tests/x.py::test_y"):
            time.sleep(30)
    assert time.monotonic() - began < 5
    # this test's own limit (the autouse fixture's) is armed again, and a
    # body inside its limit is left alone
    left, _every = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= TEST_LIMIT_S
    with time_limit(5, "tests/x.py::test_y"):
        time.sleep(0.01)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 5


_PROBE = (
    "import jax\n"
    "def suite_limit_probe(x):\n"
    "    return (x * 3 + 1).sum()\n"
    "def run_probe():\n"
    "    return float(jax.jit(suite_limit_probe)(jax.numpy.arange(7.0)))\n")


def test_the_suites_processes_share_the_programs_compile_cache():
    """``conftest.py`` turned on ``mxnet_tpu.runtime.compile_cache()``: this
    process keeps every program, however quickly it compiled, in the
    directory that function names, and a child started the way
    ``test_chip_smoke.py`` starts one (this environment, nothing more) loads
    from there what this process compiled."""
    from mxnet_tpu import runtime
    where = jax.config.jax_compilation_cache_dir
    assert where == runtime.compile_cache().path
    assert where == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_enable_compilation_cache
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    scope = {}
    exec(_PROBE, scope)
    assert scope["run_probe"]() == 70.0
    assert glob.glob(os.path.join(where, "jit_suite_limit_probe-*"))
    child = _PROBE + (
        "import json\n"
        "hits = []\n"
        "jax.monitoring.register_event_listener(\n"
        "    lambda event, **kw: hits.append(event)\n"
        "    if event == '/jax/compilation_cache/cache_hits' else None)\n"
        "print(json.dumps({'answer': run_probe(), 'hits': len(hits),\n"
        "                  'dir': jax.config.jax_compilation_cache_dir}))\n")
    proc = subprocess.run([sys.executable, "-c", child], capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["answer"] == 70.0 and got["dir"] == where
    assert got["hits"] >= 1, got
