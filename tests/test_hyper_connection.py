"""``mxnet_tpu.ops.hyper_connection`` against a NumPy loop written from the
equations (mHC, arXiv:2512.24880): a token's streams ``X (n, C)``, per
sublayer ``phi (nC, n + n + n*n)``, ``a (3,)``, ``b``; ``Hpre = sigmoid``,
``Hpost = 2 sigmoid``, ``Hres`` = ``iters`` Sinkhorn rounds (columns, then
rows) on ``exp(clip(.))``; read ``Hpre X``, write-back ``Hres X + Hpost^T
y``.  Tiny sizes on the CPU; float32 against float64."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import hyper_connection as hc

N, C, EPS = 4, 16, 1e-6


def draws(seed, lead, phi_std=0.1, a=(0.3, 0.3, 0.3), diag=1.5):
    rng = np.random.default_rng(seed)
    b = np.zeros((N * (N + 2),))
    b[2 * N:] = (diag * np.eye(N)).reshape(-1)
    return (rng.normal(size=lead + (N, C)),
            {"phi": rng.normal(size=(N * C, N * (N + 2))) * phi_std,
             "a": np.asarray(a, "float64"), "b": b},
            rng.normal(size=lead + (C,)))


def by_the_equations(X, p, y, iters, clamp):
    """One token at a time, float64: ``(Hpre, Hpost, Hres, u, X')``."""
    out = []
    for X1, y1 in zip(X.reshape((-1, N, C)), y.reshape((-1, C))):
        x = X1.reshape(-1)
        xn = x / np.sqrt((x * x).mean() + EPS)
        z = xn @ p["phi"]
        a, b = p["a"], p["b"]
        h_pre = 1 / (1 + np.exp(-(a[0] * z[:N] + b[:N])))
        h_post = 2 / (1 + np.exp(-(a[1] * z[N:2 * N] + b[N:2 * N])))
        m = np.exp(np.clip(a[2] * z[2 * N:].reshape(N, N)
                           + b[2 * N:].reshape(N, N), *clamp))
        for _ in range(iters):
            m = m / (m.sum(0, keepdims=True) + EPS)
            m = m / (m.sum(1, keepdims=True) + EPS)
        out.append((h_pre, h_post, m, h_pre @ X1,
                    m @ X1 + h_post[:, None] * y1[None, :]))
    lead = X.shape[:-2]
    return [np.stack(v).reshape(lead + v[0].shape) for v in zip(*out)]


def as_f32(p):
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


@pytest.mark.parametrize("lead", [(5,), (2, 3)])
@pytest.mark.parametrize("iters", [0, 3, 20])
def test_operator_is_the_numpy_loop(lead, iters):
    X, p, y = draws(1, lead)
    want = by_the_equations(X, p, y, iters, (-30, 30))
    Xj = jnp.asarray(X, jnp.float32)
    h_pre, h_post, h_res = hc.hc_coefficients(Xj, as_f32(p), iters, EPS,
                                              (-30.0, 30.0))
    u = hc.hc_read(Xj, h_pre)
    new = hc.hc_write(Xj, h_res, h_post, jnp.asarray(y, jnp.float32))
    for got, ref in zip((h_pre, h_post, h_res, u, new), want):
        assert got.shape == ref.shape and got.dtype == jnp.float32
        assert np.abs(np.asarray(got) - ref).max() <= 2e-5 * max(
            np.abs(ref).max(), 1.0)


@pytest.mark.parametrize("iters,lo,hi", [(20, 0.0, 1e-5), (0, 0.5, 1e9)])
def test_rounds_make_the_mix_doubly_stochastic(iters, lo, hi):
    X, p, _y = draws(2, (64,))
    _pre, _post, h_res = hc.hc_coefficients(
        jnp.asarray(X, jnp.float32), as_f32(p), iters, EPS, (-30.0, 30.0))
    worst = float(hc.sinkhorn_residual(h_res).max())
    assert lo <= worst <= hi
    assert float(h_res.min()) > 0.0
    # the residual is the largest row or column sum's distance from 1
    sums = np.concatenate([np.asarray(h_res.sum(-1)),
                           np.asarray(h_res.sum(-2))], -1)
    assert np.isclose(np.abs(sums - 1).max(), worst, rtol=1e-5, atol=1e-7)


def test_clamp_bounds_the_residual_logits_before_the_exponential():
    # logits of some hundreds: unclamped they overflow float32's exp
    X, p, _y = draws(3, (32,), phi_std=5.0, a=(1.0, 1.0, 1.0))
    Xj = jnp.asarray(X, jnp.float32)
    _pre, _post, m = hc.hc_coefficients(Xj, as_f32(p), 0, EPS, (-2.0, 2.0))
    m = np.asarray(m)
    assert np.isfinite(m).all()
    assert m.min() >= np.exp(-2.0) * (1 - 1e-6)
    assert m.max() <= np.exp(2.0) * (1 + 1e-6)
    assert (np.isclose(m, np.exp(2.0)) | np.isclose(m, np.exp(-2.0))).mean() \
        > 0.9
    _pre, _post, wide = hc.hc_coefficients(Xj, as_f32(p), 20, EPS,
                                           (-30.0, 30.0))
    assert np.isfinite(np.asarray(wide)).all()
