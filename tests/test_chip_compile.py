"""Ask the chip's compiler before the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is *described*, not attached (``jax.experimental.topologies``).  These
are the kept compiles of the main path at real widths: what the v5e compiler
would refuse on the machine with the chip, it refuses here, at no chip time.
Nothing runs, so nothing here says a result is right — ``chip_smoke.py``
does that on the chip.
"""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops import pallas_kernels  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """``SingleDeviceSharding`` on the first device of a described v5e 2x2
    host, with the persistent compilation cache off around the module: a
    compile for a described chip is written to the cache but cannot be read
    back without one, and would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler: skip
        pytest.skip(f"cannot describe a v5e topology here: {e!r}"[:300])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_text(shape, dtype, causal, sharding):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    fn = jax.jit(lambda q, k, v: pallas_kernels._fa_forward(
        q, k, v, causal, 0.125, 128, 128, False))
    return fn.lower(x, x, x).compile().as_text()


@pytest.mark.parametrize("shape,dtype", [
    ((32, 12, 128, 64), jnp.bfloat16),      # BERT-base b32 x s128
    ((32, 12, 128, 64), jnp.float32),
    ((1, 12, 8192, 64), jnp.bfloat16),      # long sequence, one row
    ((1, 12, 200, 64), jnp.bfloat16),       # padded T: keys masked by length
])
def test_flash_kernel_compiles_for_v5e(one_chip, shape, dtype):
    for causal in (False, True):
        assert "tpu_custom_call" in _flash_text(shape, dtype, causal,
                                                one_chip)


@pytest.mark.parametrize("shape,dtype,limit", [
    ((1, 12, 32768, 64), jnp.bfloat16, 16000),
    ((1, 12, 16128, 64), jnp.bfloat16, 16000),
    ((1, 12, 8064, 64), jnp.float32, 7936),
])
def test_flash_kernel_names_its_sequence_limit(shape, dtype, limit):
    """Past the whole-sequence K/V blocks' VMEM budget the kernel raises
    before the compiler does, naming the limit — it never hands back the
    dense reference instead."""
    x = jax.ShapeDtypeStruct(shape, dtype)
    with pytest.raises(ValueError, match=f"limit at this width and dtype "
                                         f"is {limit} tokens"):
        jax.eval_shape(lambda q, k, v: pallas_kernels.flash_attention(
            q, k, v), x, x, x)


def test_flash_limit_is_the_compilers(one_chip):
    """The estimate agrees with the v5e compiler on both sides of the
    limit: the largest accepted length compiles, and one block more is
    refused by the compiler too when the check is lifted."""
    assert "tpu_custom_call" in _flash_text((1, 12, 16000, 64), jnp.bfloat16,
                                            False, one_chip)
    budget = pallas_kernels._VMEM_LIMIT_BYTES
    pallas_kernels._VMEM_LIMIT_BYTES = 1 << 40
    try:
        with pytest.raises(Exception, match="vmem"):
            _flash_text((1, 12, 16128, 64), jnp.bfloat16, False, one_chip)
    finally:
        pallas_kernels._VMEM_LIMIT_BYTES = budget


@pytest.mark.parametrize("kv_dtype", ["float32", "int8", "fp8_e4m3"])
def test_decode_base_step_program_compiles_for_v5e(one_chip, kv_dtype):
    """One ``decode_base`` step program per KV pool dtype (v5e has no
    native fp8: the converts must still lower)."""
    import numpy as np
    from mxnet_tpu.serving.decode import DecodeRuntime, get_decode_model

    net = get_decode_model("decode_base")
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(4,), seq_buckets=(16,),
                       page_size=16, kv_dtype=kv_dtype, warm=False)
    b, pages = 4, rt.cache.max_pages_per_seq

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)

    args = ([sds(p) for p in rt._params],
            sds(np.zeros((b,), "int32")), sds(np.zeros((b,), "int32")),
            sds(np.zeros((b, pages), "int32")),
            sds(np.zeros((b, 2), "uint32")), sds(np.zeros((b,), "int32")),
            sds(np.zeros((b,), "float32"))) + \
        tuple(sds(p) for p in rt.cache.pools)
    compiled = rt._build_step().lower(*args).compile()
    # the donated pools come back in place: all of them alias
    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                     for p in rt.cache.pools)
    assert stats.alias_size_in_bytes >= pool_bytes
