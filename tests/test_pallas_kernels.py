"""Pallas flash attention kernel vs the full-materialization reference
(interpret mode on the CPU mesh; tests/test_chip_compile.py compiles the same
kernel for a described v5e, chip_smoke.py runs it on the chip)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_kernels import flash_attention, _reference


def _qkv(b=2, h=2, t=256, d=64, seed=0, dtype="float32"):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    ref = _reference(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_padded_seq(causal):
    """T not divisible by the block: the kernel pads, masks the padded keys
    by length (non-causal rows would otherwise attend to them with score 0)
    and slices back — it never swaps in the dense reference."""
    q, k, v = _qkv(t=200)
    out = flash_attention(q, k, v, causal, None, 128, 128, True)
    ref = _reference(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    assert out.shape == (2, 2, 200, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_gradients():
    q, k, v = _qkv(b=1, h=1, t=128, d=64)

    def loss_k(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True).sum()

    def loss_r(q, k, v):
        return _reference(q, k, v, True, 1.0 / np.sqrt(64)).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def test_flash_nd_contrib_surface():
    q, k, v = _qkv(b=1, h=1, t=128, d=64)
    out = mx.nd.contrib.flash_attention(mx.nd.array(np.asarray(q)),
                                        mx.nd.array(np.asarray(k)),
                                        mx.nd.array(np.asarray(v)))
    assert out.shape == (1, 1, 128, 64)
    assert np.isfinite(out.asnumpy()).all()
