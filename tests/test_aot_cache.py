"""serving.aot: persistent AOT program cache — round trip, bitwise
contract, and the poisoning matrix (ISSUE 18 satellite: corrupt /
truncated / wrong-version entries must fall back to a fresh compile with
a ``gateway.aot_cache_fallback`` counter, never crash or serve stale)."""
import json
import os
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving import aot
from mxnet_tpu.serving.aot import (AOT_FORMAT, _MAGIC, ProgramCache,
                                   model_signature)

# what this file stores are programs the compiler made in this process
pytestmark = pytest.mark.usefixtures("compiled_anew")

_M = len(_MAGIC)

ITEM = (24,)


@pytest.fixture(autouse=True)
def _clean_bus():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()
    faults.clear()


def _make_net():
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential(prefix="aotnet_")
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"))
        net.add(mx.gluon.nn.Dense(4))
    net.initialize()
    net.hybridize()
    return net


def _cache(tmp_path, net, salt=""):
    return ProgramCache(str(tmp_path), model_signature(net, salt=salt))


# ------------------------------------------------------------- model keys
def test_model_signature_stable_and_salted():
    a, b = _make_net(), _make_net()
    assert model_signature(a) == model_signature(b)
    assert model_signature(a) != model_signature(a, salt="geometry-v2")


def test_model_signature_tracks_param_shapes():
    a = _make_net()
    mx.random.seed(0)
    b = mx.gluon.nn.HybridSequential(prefix="aotnet_")
    with b.name_scope():
        b.add(mx.gluon.nn.Dense(32, activation="relu"))   # different width
        b.add(mx.gluon.nn.Dense(4))
    b.initialize()
    b.hybridize()
    assert model_signature(a) != model_signature(b)


# ------------------------------------------------------------- round trip
def test_compile_for_round_trip_bitwise(tmp_path):
    x = nd.array(np.random.RandomState(0).rand(4, *ITEM).astype("float32"))
    net1 = _make_net()
    c1 = _cache(tmp_path, net1)
    sig1 = net1.compile_for(x, cache=c1)
    assert c1.stores == 1 and c1.misses == 1
    y1 = net1(x).asnumpy()

    # "restarted process": same model rebuilt, loads instead of compiling
    net2 = _make_net()
    c2 = _cache(tmp_path, net2)
    sig2 = net2.compile_for(x, cache=c2)
    assert (c2.hits, c2.misses, c2.fallbacks) == (1, 0, 0)
    assert sig1 == sig2
    assert net2._cached_op._aot, "AOT executable not installed"
    y2 = net2(x).asnumpy()
    assert (y1 == y2).all(), "warm-cache outputs must be bitwise identical"


def test_compile_grid_through_cache(tmp_path):
    def make_example(b):
        return [nd.array(np.zeros((b,) + ITEM, "float32"))]

    net1 = _make_net()
    c1 = _cache(tmp_path, net1)
    sigs1 = net1.compile_grid(make_example, [1, 2, 4], cache=c1)
    assert c1.stores == 3
    net2 = _make_net()
    c2 = _cache(tmp_path, net2)
    sigs2 = net2.compile_grid(make_example, [1, 2, 4], cache=c2)
    assert c2.hits == 3 and c2.misses == 0
    assert sigs1 == sigs2
    # signatures registered as compiled — serving's zero-recompile check
    assert sigs2[2] in net2.compiled_signatures(training=False)


def test_aot_hit_skips_recompile_telemetry(tmp_path):
    x = nd.array(np.zeros((2,) + ITEM, "float32"))
    net1 = _make_net()
    net1.compile_for(x, cache=_cache(tmp_path, net1))
    net2 = _make_net()
    net2.compile_for(x, cache=_cache(tmp_path, net2))
    telemetry.enable()
    net2(x)
    counters = telemetry.snapshot()["counters"]
    assert not any(k.startswith("cachedop.recompiles")
                   for k in counters), counters


def test_load_or_build(tmp_path):
    import jax
    import jax.numpy as jnp
    pc = ProgramCache(str(tmp_path), "m1")
    fn = jax.jit(lambda a: jnp.sin(a) * 2)
    x = np.linspace(0, 1, 7, dtype="float32")
    built, meta, loaded = pc.load_or_build("sin2", fn, (x,),
                                           extra={"k": [1, 2]})
    assert not loaded and pc.stores == 1
    hit, meta2, loaded2 = pc.load_or_build("sin2", fn, (x,))
    assert loaded2 and meta2 == {"k": [1, 2]}
    assert (np.asarray(built(x)) == np.asarray(hit(x))).all()


# ------------------------------------------------------- poisoning matrix
def _seed_entry(tmp_path):
    import jax
    import jax.numpy as jnp
    pc = ProgramCache(str(tmp_path), "victim")
    fn = jax.jit(lambda a: a + 1)
    x = np.zeros((3,), "float32")
    pc.load_or_build("prog", fn, (x,))
    return pc, pc.path("prog"), fn, x


def _fallback_reasons():
    by_label = telemetry.snapshot()["counters_by_label"]
    return by_label.get("gateway.aot_cache_fallback", {})


@pytest.mark.parametrize("poison,reason", [
    (lambda raw: raw[:len(raw) // 2], "truncated"),
    (lambda raw: b"GARBAGE!" + raw[8:], "bad_magic"),
    (lambda raw: raw[:-20] + bytes(20), "crc"),
    (lambda raw: raw[:10], "truncated"),
    (lambda raw: b"", "bad_magic"),
])
def test_poisoned_entry_falls_back(tmp_path, poison, reason):
    pc, path, fn, x = _seed_entry(tmp_path)
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(poison(raw))
    telemetry.enable()
    fresh = ProgramCache(str(tmp_path), "victim")
    out, meta, loaded = fresh.load_or_build("prog", fn, (x,))
    assert not loaded and fresh.fallbacks == 1
    assert (np.asarray(out(x)) == 1).all()     # fresh compile still works
    assert any(f'reason="{reason}"' in k for k in _fallback_reasons()), \
        _fallback_reasons()


def _rewrite_header(path, **patch):
    raw = open(path, "rb").read()
    magic = raw[:_M]
    (hlen,) = struct.unpack("<I", raw[_M:_M + 4])
    header = json.loads(raw[_M + 4:_M + 4 + hlen].decode())
    header.update(patch)
    blob = raw[_M + 4 + hlen:]
    hjson = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(magic + struct.pack("<I", len(hjson)) + hjson + blob)


@pytest.mark.parametrize("patch,reason", [
    ({"format": AOT_FORMAT + 1}, "format_version"),
    ({"jaxlib": "0.0.0"}, "env_jaxlib"),
    ({"backend": "tpu-v9"}, "env_backend"),
    ({"model_key": "someone-else"}, "model_key"),
    ({"name": "other-prog"}, "entry_name"),
])
def test_version_and_identity_mismatch_falls_back(tmp_path, patch, reason):
    pc, path, fn, x = _seed_entry(tmp_path)
    _rewrite_header(path, **patch)
    telemetry.enable()
    fresh = ProgramCache(str(tmp_path), "victim")
    out, meta, loaded = fresh.load_or_build("prog", fn, (x,))
    assert not loaded and fresh.fallbacks == 1
    assert (np.asarray(out(x)) == 1).all()
    assert any(f'reason="{reason}"' in k for k in _fallback_reasons()), \
        _fallback_reasons()


def test_malicious_pickle_refused(tmp_path):
    """A crc-consistent entry whose blob references a module outside the
    jax/numpy allowlist must fall back, not execute."""
    import pickle
    pc, path, fn, x = _seed_entry(tmp_path)
    evil = pickle.dumps((os.system, "echo pwned"))
    raw = open(path, "rb").read()
    (hlen,) = struct.unpack("<I", raw[_M:_M + 4])
    header = json.loads(raw[_M + 4:_M + 4 + hlen].decode())
    import zlib
    header["payload_len"] = len(evil)
    header["crc32"] = zlib.crc32(evil) & 0xffffffff
    hjson = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(raw[:_M] + struct.pack("<I", len(hjson)) + hjson + evil)
    telemetry.enable()
    fresh = ProgramCache(str(tmp_path), "victim")
    assert fresh.load("prog") is None
    assert any('reason="unpickle"' in k for k in _fallback_reasons()), \
        _fallback_reasons()


def test_missing_entry_is_plain_miss(tmp_path):
    pc = ProgramCache(str(tmp_path), "empty")
    telemetry.enable()
    assert pc.load("never-stored") is None
    assert pc.fallbacks == 0 and pc.misses == 1
    counters = telemetry.snapshot()["counters"]
    assert not any(k.startswith("gateway.aot_cache_fallback")
                   for k in counters)


def test_store_failure_is_nonfatal(tmp_path):
    """A failed commit (injected at the aot.write durable site) warns and
    returns False — serving never dies because a cache write did."""
    import jax
    import jax.numpy as jnp
    pc = ProgramCache(str(tmp_path), "m")
    fn = jax.jit(lambda a: a * 3)
    x = np.ones((2,), "float32")
    telemetry.enable()
    with faults.scope("aot.write:fail:1"):
        out, meta, loaded = pc.load_or_build("p", fn, (x,))
    assert not loaded
    assert (np.asarray(out(x)) == 3).all()     # the compile still served
    assert pc.entries() == []                  # nothing torn on disk
    counters = telemetry.snapshot()["counters"]
    assert counters.get("gateway.aot_cache_store_failures") == 1


def test_env_keyed_directories(tmp_path):
    pc = ProgramCache(str(tmp_path), "m")
    import jax
    assert f"aot-v{AOT_FORMAT}" in pc.dir
    assert jax.__version__ in pc.dir
    assert pc.dir.endswith("m")


def test_as_program_cache_passthrough(tmp_path):
    net = _make_net()
    pc = ProgramCache(str(tmp_path), "m")
    assert aot.as_program_cache(None, net) is None
    assert aot.as_program_cache(pc, net) is pc
    derived = aot.as_program_cache(str(tmp_path), net, salt="s")
    assert isinstance(derived, ProgramCache)
    assert derived.model_key == model_signature(net, salt="s")
