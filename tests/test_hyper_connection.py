"""``mxnet_tpu.ops.hyper_connection`` against a NumPy loop written from the
equations (mHC, arXiv:2512.24880): a token's streams ``X (n, C)``, per
sublayer ``phi (nC, n + n + n*n)``, ``a (3,)``, ``b``; ``Hpre = sigmoid``,
``Hpost = 2 sigmoid``, ``Hres`` = ``iters`` Sinkhorn rounds (columns, then
rows) on ``exp(clip(.))``; read ``Hpre X``, write-back ``Hres X + Hpost^T
y``.  Tiny sizes on the CPU; float32 against float64.  Each test holds
the ``jax.numpy`` definition and, at 128-wide streams under the interpreter,
the two kernels the chip runs in its place (``ops.pallas_kernels.hc_pre``,
``hc_post``): a step's rows in one block, and a prefill's tokens that do not
fill their last block of 128."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import hyper_connection as hc
from mxnet_tpu.ops import pallas_kernels

N, EPS = 4, 1e-6
#: stream width by form: the kernels take whole lane tiles
WIDTH = {"definition": 16, "kernels": 128}


def draws(seed, lead, form="definition", phi_std=0.1, a=(0.3, 0.3, 0.3),
          diag=1.5):
    rng, C = np.random.default_rng(seed), WIDTH[form]
    b = rng.normal(size=(N * (N + 2),)) * 0.2
    b[2 * N:] = (diag * np.eye(N)).reshape(-1)
    return (rng.normal(size=lead + (N, C)),
            {"phi": rng.normal(size=(N * C, N * (N + 2))) * phi_std
             * 4 / np.sqrt(C), "a": np.asarray(a, "float64"), "b": b},
            rng.normal(size=lead + (C,)))


def mixing(form, X, p, iters, clamp, live=None):
    """``(Hpre, Hpost, Hres, u, write)`` of float32 streams ``X`` by the
    definition or by the two kernels under the interpreter; ``write(y)`` is
    the streams the sublayer leaves.  ``live`` is the kernels' to use."""
    if form == "definition":
        h_pre, h_post, h_res = hc.hc_coefficients(X, p, iters, EPS, clamp)
        return (h_pre, h_post, h_res, hc.hc_read(X, h_pre),
                lambda y: hc.hc_write(X, h_res, h_post, y))
    u, coef = pallas_kernels.hc_pre(X, p["phi"].T, p["a"], p["b"], live,
                                    iters=iters, eps=EPS, clamp=clamp,
                                    interpret=True)
    return hc.coef_parts(coef, X.shape[:-2], N) + (
        u, lambda y: pallas_kernels.hc_post(X, coef, y, interpret=True))


def by_the_equations(X, p, y, iters, clamp):
    """One token at a time, float64: ``(Hpre, Hpost, Hres, u, X')``."""
    out, C = [], X.shape[-1]
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


@pytest.mark.parametrize("form,lead", [
    ("definition", (5,)), ("definition", (2, 3)),
    ("kernels", (1,)), ("kernels", (7,)), ("kernels", (32,)),
    ("kernels", (1, 300))])
@pytest.mark.parametrize("iters", [0, 1, 3, 20])
def test_operator_is_the_numpy_loop(form, lead, iters):
    X, p, y = draws(1, lead, form)
    want = by_the_equations(X, p, y, iters, (-30, 30))
    Xj = jnp.asarray(X, jnp.float32)
    *coefs, u, write = mixing(form, Xj, as_f32(p), iters, (-30.0, 30.0))
    new = write(jnp.asarray(y, jnp.float32))
    for got, ref in zip((*coefs, u, new), want):
        assert got.shape == ref.shape and got.dtype == jnp.float32
        assert np.abs(np.asarray(got) - ref).max() <= 2e-5 * max(
            np.abs(ref).max(), 1.0)


@pytest.mark.parametrize("lead", [(1,), (7,), (32,), (1, 300)])
def test_kernels_are_the_definition(lead):
    """Float32 against float32 on the same draws: the kernels' coefficient
    product sums in another order and nothing else differs, so the two lie
    rounding apart (the NumPy loop is 2e-5 from either)."""
    X, p, y = draws(4, lead, "kernels")
    Xj, yj = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    both = [mixing(form, Xj, as_f32(p), 20, (-30.0, 30.0))
            for form in ("definition", "kernels")]
    for want, got in zip(*((*m[:4], m[4](yj)) for m in both)):
        assert got.shape == want.shape
        assert float(jnp.abs(got - want).max()) <= 4e-6 * max(
            float(jnp.abs(want).max()), 1.0)
    resid = [float(hc.sinkhorn_residual(m[2]).max()) for m in both]
    assert abs(resid[0] - resid[1]) <= 2e-6 and resid[1] <= 1e-5


@pytest.mark.parametrize("live", [(0, 1, 9), (30,), ()])
def test_padding_rows_are_passed_over_eight_at_a_time(live):
    """A step's 32 rows with a few live ones: every live row, and every row
    that shares its eight with one, reads what the definition reads; eight
    rows of padding read the coefficients of a zero stream (the bias
    alone), and their streams still mix, finitely."""
    X, p, y = draws(7, (32,), "kernels")
    Xj, yj = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    mask = np.zeros((32,), bool)
    mask[list(live)] = True
    want = mixing("definition", Xj, as_f32(p), 20, (-30.0, 30.0))
    zero = mixing("definition", 0 * Xj, as_f32(p), 20, (-30.0, 30.0))
    got = mixing("kernels", Xj, as_f32(p), 20, (-30.0, 30.0),
                 jnp.asarray(mask))
    seen = np.repeat(mask.reshape(4, 8).any(1), 8)
    for g, w, z in zip(got[:3], want[:3], zero[:3]):
        assert np.allclose(np.asarray(g)[seen], np.asarray(w)[seen],
                           atol=4e-6)
        assert np.allclose(np.asarray(g)[~seen], np.asarray(z)[~seen],
                           atol=4e-6)
    assert np.allclose(np.asarray(got[3])[seen], np.asarray(want[3])[seen],
                       atol=1e-5)
    assert np.isfinite(np.asarray(got[4](yj))).all()


@pytest.mark.parametrize("form", ["definition", "kernels"])
@pytest.mark.parametrize("iters,lo,hi", [(20, 0.0, 1e-5), (0, 0.5, 1e9)])
def test_rounds_make_the_mix_doubly_stochastic(form, iters, lo, hi):
    X, p, _y = draws(2, (64,), form)
    _pre, _post, h_res, _u, _write = mixing(
        form, jnp.asarray(X, jnp.float32), as_f32(p), iters, (-30.0, 30.0))
    worst = float(hc.sinkhorn_residual(h_res).max())
    assert lo <= worst <= hi
    assert float(h_res.min()) > 0.0
    # the residual is the largest row or column sum's distance from 1
    sums = np.concatenate([np.asarray(h_res.sum(-1)),
                           np.asarray(h_res.sum(-2))], -1)
    assert np.isclose(np.abs(sums - 1).max(), worst, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("form", ["definition", "kernels"])
def test_clamp_bounds_the_residual_logits_before_the_exponential(form):
    # logits of some hundreds: unclamped they overflow float32's exp
    X, p, _y = draws(3, (32,), form, phi_std=5.0, a=(1.0, 1.0, 1.0))
    Xj = jnp.asarray(X, jnp.float32)
    m = np.asarray(mixing(form, Xj, as_f32(p), 0, (-2.0, 2.0))[2])
    assert np.isfinite(m).all()
    assert m.min() >= np.exp(-2.0) * (1 - 1e-6)
    assert m.max() <= np.exp(2.0) * (1 + 1e-6)
    # both edges are reached, and nearly every entry lies on one
    assert np.isclose(m, np.exp(2.0)).any() \
        and np.isclose(m, np.exp(-2.0)).any()
    assert (np.isclose(m, np.exp(2.0)) | np.isclose(m, np.exp(-2.0))).mean() \
        > 0.9
    wide = mixing(form, Xj, as_f32(p), 20, (-30.0, 30.0))[2]
    assert np.isfinite(np.asarray(wide)).all()


def test_coefficient_tile_is_its_parts():
    X, p, _y = draws(5, (2, 3))
    parts = hc.hc_coefficients(jnp.asarray(X, jnp.float32), as_f32(p), 20,
                               EPS, (-30.0, 30.0))
    tile = hc.coef_tile(*parts)
    assert tile.shape == (6, hc.COEF_LANES)
    assert not np.asarray(tile[:, N * (N + 2):]).any()
    for got, want in zip(hc.coef_parts(tile, (2, 3), N), parts):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("form,counts", [
    ("kernels", {'{kind="plain",tokens="5"}': 2}), ("definition", {})])
def test_sublayer_lowers_the_definition_for_the_cpu(form, counts):
    """``LatentMoELM._sublayer`` is the kernels' one caller: lowered for the
    CPU it is the definition's two halves and ``decode.hc.lowered`` counts
    ``kind="plain"`` once a half (for the chip: ``tests/test_chip_compile.
    py``); a width that is not whole lane tiles never asks."""
    import jax
    from mxnet_tpu.serving.decode import LatentMoELM
    from mxnet_tpu.test_utils import counted
    net = LatentMoELM(vocab_size=32, hidden_size=WIDTH[form], num_layers=1,
                      num_heads=2, first_k_dense_replace=1,
                      intermediate_size=32, hc_mult=N, dtype="float32")
    X, p, _y = draws(6, (5,), form)
    X, p = jnp.asarray(X, jnp.float32), as_f32(p)
    params = {"l0_hc_attn_phi": p["phi"].T, "l0_hc_attn_a": p["a"],
              "l0_hc_attn_b": p["b"],
              "l0_norm_attn": jnp.ones((WIDTH[form],))}
    fn = jax.jit(lambda X: net._sublayer(params, 0, "attn", X,
                                         lambda m: (2.0 * m,)))
    assert counted("decode.hc.lowered", lambda: fn.lower(X)) == counts
    h_pre, h_post, h_res = hc.hc_coefficients(X, p, net.hc_iters, net.hc_eps,
                                              net.hc_clamp)
    u = hc.hc_read(X, h_pre)
    m = u * jax.lax.rsqrt((u * u).mean(-1, keepdims=True) + net.eps)
    assert np.allclose(np.asarray(fn(X)),
                       np.asarray(hc.hc_write(X, h_res, h_post, 2.0 * m)),
                       atol=1e-5)
