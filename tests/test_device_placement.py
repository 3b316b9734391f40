"""Nothing hides the device: accelerator contexts do not fall back to the
host, a serving runtime decides in one place where it runs and says so, the
compile cache is placed from outside, and importing the framework takes no
device."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import runtime as mx_runtime
from mxnet_tpu.context import context_from_jax_device
from mxnet_tpu.serving import ModelRuntime
from mxnet_tpu.serving.decode import DecodeSession, get_decode_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ contexts
@pytest.mark.parametrize("ctx", [mx.tpu(0), mx.gpu(0)], ids=str)
def test_accelerator_context_raises_on_a_cpu_only_process(ctx):
    with pytest.raises(RuntimeError, match="no TPU"):
        ctx.jax_device()
    with pytest.raises(RuntimeError, match="does not fall back"):
        mx.nd.zeros((2,), ctx=ctx)


def test_a_tpu_device_maps_to_the_tpu_context():
    assert context_from_jax_device(
        types.SimpleNamespace(platform="tpu", id=2)) == mx.tpu(2)
    assert context_from_jax_device(jax.devices()[1]) == mx.cpu(1)


def test_reset_ctx_moves_data_and_gradient():
    net = mx.gluon.nn.Dense(3, in_units=2)
    net.initialize(ctx=mx.cpu(1))
    w = net.weight.data()
    before = w.asnumpy()
    net.collect_params().reset_ctx(mx.cpu(2))
    want = {jax.devices()[2]}
    assert net.weight.data() is w                   # same handle, moved
    assert w._data.devices() == want
    assert net.weight.grad()._data.devices() == want
    assert net.weight.list_ctx() == [mx.cpu(2)]
    np.testing.assert_array_equal(w.asnumpy(), before)
    x = mx.nd.ones((4, 2), ctx=mx.cpu(2))
    with mx.autograd.record():
        loss = net(x).sum()
    loss.backward()
    assert net.weight.grad()._data.devices() == want


# ------------------------------------------------------------------- serving
def _tiny_decode():
    mx.random.seed(0)
    return get_decode_model("decode_tiny", vocab_size=96, max_length=32,
                            units=32, num_heads=2)


@pytest.mark.parametrize("ctx", [None, mx.cpu(1)], ids=["no-ctx", "cpu(1)"])
def test_decode_session_lands_on_its_device_and_names_it(ctx):
    """A block initialised with no ``ctx`` (or committed to another device)
    runs where the session runs: parameters and both KV pools committed to
    ONE device, which ``stats()`` names."""
    net = _tiny_decode()
    net.initialize(ctx=ctx)
    # nothing compiles or runs here: placement is decided at construction
    # (chip_smoke's serve phase checks the pools again after traffic)
    sess = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8,),
                         page_size=8, warm=False, start=False)
    try:
        device = sess.runtime.device
        assert device == jax.local_devices()[0]
        for p in net.collect_params().values():
            assert p.data()._data.devices() == {device}, p.name
        for pool in sess.cache.pools:
            assert pool.committed and pool.devices() == {device}
        stats = sess.stats()
        assert stats["platform"] == "cpu"
        assert stats["device_kind"] == device.device_kind
    finally:
        sess.close(drain=False)


def test_model_runtime_moves_a_block_committed_elsewhere():
    mx.random.seed(1)
    net = mx.gluon.nn.Dense(4, in_units=8)
    net.initialize(ctx=mx.cpu(3))
    w, b = net.weight.data().asnumpy(), net.bias.data().asnumpy()
    rt = ModelRuntime(net, item_shapes=(8,), max_batch=2)
    assert rt.device == jax.local_devices()[0]
    assert net.weight.data()._data.devices() == {rt.device}
    x = np.arange(8, dtype="float32")
    np.testing.assert_allclose(rt(x), x @ w.T + b, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- compile cache
@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them: the
    pytest process must never really turn the persistent cache on."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda fn: None)
    return calls


def test_compile_cache_dir_set_from_outside_is_left_alone(
        config_updates, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    cache = mx_runtime.compile_cache()
    assert cache.path == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_default_is_one_fixed_path(config_updates,
                                                 monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache = mx_runtime.compile_cache()
    assert cache.path == os.path.join(ROOT, ".jax_cache")
    assert config_updates["jax_compilation_cache_dir"] == cache.path
    cache._on_event("/jax/compilation_cache/cache_hits")
    cache._on_event("/jax/compilation_cache/cache_misses")
    cache._on_event("/jax/compilation_cache/cache_hits")
    assert cache.stats() == {"dir": cache.path, "hits": 2, "misses": 1}


def test_fresh_process_same_cache_path_and_no_backend_at_import(tmp_path):
    """In another process, from another directory: the same cache path; and
    importing the framework plus standing up a gateway front end initialises
    no JAX backend — on the chip machine that would take the chip from the
    device-owner child."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.serving.gateway import Gateway\n"
        "from mxnet_tpu.serving.fleet import Supervisor\n"
        "from mxnet_tpu.runtime import compile_cache\n"
        "from jax._src import xla_bridge\n"
        "mx.random.seed(3)\n"
        "gw = Gateway(name='front'); gw.close()\n"
        "cache = compile_cache()\n"
        "import jax\n"
        "print(json.dumps({'path': cache.path,\n"
        "    'config': jax.config.jax_compilation_cache_dir,\n"
        "    'backend': xla_bridge.backends_are_initialized()}))\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"path": os.path.join(ROOT, ".jax_cache"),
                   "config": os.path.join(ROOT, ".jax_cache"),
                   "backend": False}
