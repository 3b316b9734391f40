"""Pallas flash attention kernels vs the dense formula (interpret mode on
the CPU mesh; tests/test_chip_compile.py compiles the same kernels for a
described v5e, chip_smoke.py runs them on the chip).

Under the interpreter the kernels' products are float32, as XLA's are on
the CPU; ``jax.default_matmul_precision("bfloat16")`` shows the chip's."""
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
    """The causal backward, blockwise: dq, dk and dv come from the backward
    kernel, which skips the key blocks past the diagonal."""
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


@pytest.mark.parametrize("t", [128, 200, 256])
@pytest.mark.parametrize("rate", [0.0, 0.1])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_mask_and_dropout_match_dense(masked, rate, t):
    """Forward and dq, dk, dv of the kernels against the dense formula
    given the SAME keep-mask, with and without padded keys; 200 is padded
    to 256 inside, the padding masked out of both passes."""
    b, h, d = 2, 2, 64
    q, k, v = _qkv(b, h, t, d, seed=t)
    rng = np.random.RandomState(t + 1)
    w = jnp.asarray(rng.randn(b, h, t, d), "float32")
    kv_mask = keep = None
    if masked:
        lens = np.array([t, t // 2 + 3])
        kv_mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None],
                              "float32")
    if rate:
        keep = jax.random.bernoulli(jax.random.PRNGKey(t), 1.0 - rate,
                                    (b * h, t, t)).astype(jnp.int8)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    kernel = lambda q, k, v: flash_attention(
        q, k, v, False, None, None, None, True, kv_mask, keep, rate)
    dense = lambda q, k, v: _reference(q, k, v, False, 1.0 / np.sqrt(d),
                                       kv_mask, keep, rate)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(dense(q, k, v)), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                                   atol=2e-4)


def test_flash_chip_products_are_bfloat16():
    """On the chip the kernels round what enters a product to bfloat16 and
    accumulate in float32, as XLA does to float32 operands there: asked for
    on the CPU, the answer moves by a bfloat16's rounding, no more."""
    q, k, v = _qkv(b=1, h=2, t=128, d=64)
    keep = jax.random.bernoulli(jax.random.PRNGKey(0), 0.9,
                                (2, 128, 128)).astype(jnp.int8)
    exact = flash_attention(q, k, v, False, None, None, None, True, None,
                            keep, 0.1)
    with jax.default_matmul_precision("bfloat16"):
        out = flash_attention(q, k, v, False, None, None, None, True, None,
                              keep, 0.1)
    err = float(jnp.max(jnp.abs(out - exact)))
    assert 1e-5 < err < 5e-2, err


def test_flash_dropout_op_draws_dropouts_mask():
    """``nd.contrib.flash_attention_dropout`` keeps what ``Dropout`` on the
    dense (B * H, T, T) probabilities keeps under the same key, draws one
    key a call in either mode, and is plain attention outside training."""
    from mxnet_tpu import autograd
    b, h, t, d, rate = 1, 2, 128, 64, 0.25
    q, k, v = (mx.nd.array(np.asarray(x)) for x in _qkv(b, h, t, d))
    mask = mx.nd.array((np.arange(t)[None, :] < 100).astype("float32"))
    mx.random.seed(11)
    with autograd.train_mode():
        got = mx.nd.contrib.flash_attention_dropout(q, k, v, mask, p=rate)
        after = mx.nd.random.uniform(shape=(4,)).asnumpy()
    mx.random.seed(11)
    with autograd.train_mode():
        s = mx.nd.batch_dot(q.reshape((-3, 0, 0)), k.reshape((-3, 0, 0)),
                            transpose_b=True) / np.sqrt(d)
        s = s + ((1.0 - mask) * -1e30).reshape((b, 1, t))
        p = mx.nd.Dropout(mx.nd.softmax(s, axis=-1), p=rate)
        want = mx.nd.batch_dot(p, v.reshape((-3, 0, 0)))
        after_dense = mx.nd.random.uniform(shape=(4,)).asnumpy()
    plain = mx.nd.contrib.flash_attention_dropout(q, k, v, mask, p=rate)
    unmasked = mx.nd.contrib.flash_attention(q, k, v, mask)
    np.testing.assert_allclose(got.asnumpy().reshape(b * h, t, d),
                               want.asnumpy(), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(after, after_dense)
    np.testing.assert_array_equal(plain.asnumpy(), unmasked.asnumpy())


def test_flash_key_gradients_sum_to_zero_at_the_chips_precision():
    """Scores do not move when every key of a head moves by one vector, so
    a head's key gradients sum to zero; with bfloat16 products the rounding
    of dS leaves 5% of dK's size there (all of the key bias's gradient, and
    noise), which the backward takes out over the unmasked keys.  A masked
    key's gradient stays exactly zero."""
    q, k, v = _qkv(b=2, h=2, t=256, d=64, seed=3)
    w = _qkv(b=2, h=2, t=256, d=64, seed=4)[0]
    keep = jax.random.bernoulli(jax.random.PRNGKey(2), 0.9,
                                (4, 256, 256)).astype(jnp.int8)
    kv_mask = jnp.asarray(np.arange(256)[None, :]
                          < np.array([[256], [150]]), "float32")
    with jax.default_matmul_precision("bfloat16"):
        dk = jax.grad(lambda k: (flash_attention(
            q, k, v, False, None, None, None, True, kv_mask, keep, 0.1)
            * w).sum())(k)
    dk = np.asarray(dk)
    assert np.abs(dk.sum(axis=2)).max() < 1e-4 * np.abs(dk).max()
    assert not dk[1, :, 150:].any() and dk[1, :, :150].any()
