"""``mxnet_tpu.ops.ssm``: the Mamba-2 mathematics in its three forms (the
sequential recurrence, the chunked scan, one step) agree; padding behind a
row's true length leaves the state and the convolution's tail as of that
length; the groups-to-heads mapping and the gated group norm are what their
equations say."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import ssm


def _inputs(b=2, L=300, H=8, P=4, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, L, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (b, L, G, N))
    C = jax.random.normal(k[4], (b, L, G, N))
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, B, C, D


def _by_hand(x, dt, A, B, C, D):
    """numpy, float64, loops: the recurrence as the docstring writes it."""
    x, dt, A, B, C, D = (np.asarray(v, "float64") for v in
                         (x, dt, A, B, C, D))
    b, L, H, P = x.shape
    G, N = B.shape[-2:]
    y = np.zeros((b, L, H, P))
    S = np.zeros((b, H, P, N))
    for t in range(L):
        for h in range(H):
            g = h // (H // G)
            a = np.exp(dt[:, t, h] * A[h])
            S[:, h] = a[:, None, None] * S[:, h] + \
                (dt[:, t, h, None] * x[:, t, h])[:, :, None] \
                * B[:, t, g][:, None, :]
            y[:, t, h] = np.einsum("bpn,bn->bp", S[:, h], C[:, t, g]) \
                + D[h] * x[:, t, h]
    return y, S


def test_sequential_recurrence_is_the_equations_by_hand():
    args = _inputs(b=1, L=12, H=4, P=3, G=2, N=5)
    y, S = ssm.ssm_scan_sequential(*args)
    y0, S0 = _by_hand(*args)
    np.testing.assert_allclose(y, y0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, S0, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("L,chunk", [(300, 128), (128, 128), (129, 128),
                                     (37, 128), (97, 16), (256, 64)])
def test_chunked_scan_is_the_sequential_recurrence(L, chunk):
    """Float32 at the highest precision, lengths that are and are not whole
    chunks: outputs and the final state agree to rounding."""
    args = _inputs(L=L)
    y0, S0 = ssm.ssm_scan_sequential(*args)
    y1, S1 = ssm.ssm_scan_chunked(*args, chunk=chunk, dtype="float32")
    assert y1.shape == y0.shape and S1.shape == S0.shape
    np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(S1, S0, rtol=1e-4, atol=2e-5)


def test_chunked_scan_in_bfloat16_products_stays_near():
    """bfloat16 within-chunk products, float32 accumulation and state: 8
    bits of mantissa on each operand of three products; outputs of order 1
    agree to a few hundredths."""
    args = _inputs(L=256, seed=3)
    y0, S0 = ssm.ssm_scan_sequential(*args)
    y1, S1 = ssm.ssm_scan_chunked(*args, chunk=64, dtype="bfloat16")
    scale = float(jnp.abs(y0).mean())
    assert float(jnp.abs(y1 - y0).mean()) < 0.01 * scale
    assert float(jnp.abs(S1 - S0).max()) < 0.02 * float(jnp.abs(S0).max())


def test_padding_leaves_the_state_as_of_the_true_length():
    """``dt`` = 0 behind ``lengths``: the state a padded scan returns is the
    state of the scan over the real tokens alone, whatever the padding
    holds, and the outputs before it do not move."""
    x, dt, A, B, C, D = _inputs(L=160)
    lengths = jnp.array([101, 160])
    live = jnp.arange(160)[None, :, None] < lengths[:, None, None]
    y1, S1 = ssm.ssm_scan_chunked(x, jnp.where(live, dt, 0.0), A, B, C, D,
                                  chunk=32)
    y0, S0 = ssm.ssm_scan_sequential(x[:1, :101], dt[:1, :101], A,
                                     B[:1, :101], C[:1, :101], D)
    np.testing.assert_allclose(S1[0], S0[0], rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(y1[0, :101], y0[0], rtol=1e-4, atol=2e-5)
    # garbage in the padding changes nothing that is kept
    junk = x.at[0, 101:].set(1e3)
    _y, S2 = ssm.ssm_scan_chunked(junk, jnp.where(live, dt, 0.0), A, B, C,
                                  D, chunk=32)
    np.testing.assert_array_equal(S2[0], S1[0])


def test_prefill_state_then_single_steps_is_the_scan_over_the_whole():
    """The hand-over: the chunked scan over a prompt, then N single steps on
    its state, equals the sequential scan over prompt + N tokens."""
    x, dt, A, B, C, D = _inputs(L=90)
    n = 70
    _y, S = ssm.ssm_scan_chunked(x[:, :n], dt[:, :n], A, B[:, :n], C[:, :n],
                                 D, chunk=16)
    ys = []
    for t in range(n, 90):
        S, y = ssm.ssm_step(S, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        ys.append(y)
    y0, S0 = ssm.ssm_scan_sequential(x, dt, A, B, C, D)
    np.testing.assert_allclose(jnp.stack(ys, 1), y0[:, n:], rtol=1e-4,
                               atol=2e-5)
    np.testing.assert_allclose(S, S0, rtol=1e-4, atol=2e-5)


def test_heads_read_their_group():
    """Head ``h`` of 8 over 2 groups reads group ``h // 4``: with ``B`` and
    ``C`` zero in group 1, heads 4-7 see only ``D x``."""
    x, dt, A, B, C, D = _inputs(L=20)
    v = jnp.arange(2 * 3).reshape(2, 3).astype(jnp.float32)
    np.testing.assert_array_equal(
        ssm.heads_from_groups(v, 8),
        np.repeat(np.asarray(v), 4, axis=0))
    B = B.at[:, :, 1].set(0.0)
    for scan in (ssm.ssm_scan_sequential,
                 lambda *a: ssm.ssm_scan_chunked(*a, chunk=8)):
        y, S = scan(x, dt, A, B, C, D)
        np.testing.assert_allclose(y[:, :, 4:], D[4:, None] * x[:, :, 4:],
                                   rtol=1e-5, atol=1e-6)
        assert float(jnp.abs(S[:, 4:]).max()) == 0.0
        assert float(jnp.abs(S[:, :4]).max()) > 0.0


def test_causal_conv_its_tail_and_its_step():
    """The convolution over a whole sequence by hand; the tail is the last
    K - 1 REAL inputs (zeros before the sequence); a step on that tail gives
    what the whole-sequence form gives one position on."""
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    u = jax.random.normal(k[0], (2, 11, 6))
    w = jax.random.normal(k[1], (6, 4))
    bias = jax.random.normal(k[2], (6,))
    out = ssm.causal_conv(u, w, bias)
    un, wn, bn = (np.asarray(v, "float64") for v in (u, w, bias))
    for t in (0, 2, 10):
        pre = bn.copy()
        for j in range(4):
            if t - 3 + j >= 0:
                pre = pre + wn[:, j] * un[0, t - 3 + j]
        np.testing.assert_allclose(out[0, t], pre / (1 + np.exp(-pre)),
                                   rtol=1e-5, atol=1e-6)
    lengths = jnp.array([2, 9])
    tail = ssm.conv_tail(u, lengths, 4)
    np.testing.assert_array_equal(tail[0, 0], np.zeros(6))      # before it
    np.testing.assert_array_equal(tail[0, 1:], u[0, 0:2])
    np.testing.assert_array_equal(tail[1], u[1, 6:9])
    new_tail, y = ssm.conv_step(tail, jnp.stack([u[0, 2], u[1, 9]]), w, bias)
    np.testing.assert_allclose(y[0], out[0, 2], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[1], out[1, 9], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(new_tail[1], u[1, 7:10])
    # the tail keeps its dtype through a step
    t16, _y = ssm.conv_step(tail.astype(jnp.bfloat16), u[:, 0], w, bias)
    assert t16.dtype == jnp.bfloat16


def test_gated_group_norm_by_hand():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    y = jax.random.normal(k[0], (3, 12))
    z = jax.random.normal(k[1], (3, 12))
    gain = jax.random.normal(k[2], (12,))
    got = ssm.gated_group_norm(y, z, gain, groups=3, eps=1e-5)
    yn, zn, gn = (np.asarray(v, "float64") for v in (y, z, gain))
    v = yn * zn / (1 + np.exp(-zn))
    want = np.zeros_like(v)
    for g in range(3):
        s = v[:, 4 * g:4 * g + 4]
        want[:, 4 * g:4 * g + 4] = s / np.sqrt(
            (s * s).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * gn, rtol=1e-5, atol=1e-6)
