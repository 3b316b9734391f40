"""Extended operator contract tests (mirrors more of the reference's
``tests/python/unittest/test_operator.py`` surface)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import test_utils as tu


def test_order_ops():
    a = np.array([[3.0, 1.0, 2.0], [0.0, -1.0, 5.0]], dtype="float32")
    x = mx.nd.array(a)
    np.testing.assert_array_equal(mx.nd.sort(x, axis=1).asnumpy(),
                                  np.sort(a, axis=1))
    np.testing.assert_array_equal(mx.nd.argsort(x, axis=1).asnumpy(),
                                  np.argsort(a, axis=1, kind="stable"))
    np.testing.assert_array_equal(mx.nd.argmax(x, axis=1).asnumpy(),
                                  a.argmax(1))
    np.testing.assert_array_equal(mx.nd.argmin(x, axis=1).asnumpy(),
                                  a.argmin(1))
    top = mx.nd.topk(x, k=2, axis=1, ret_typ="value")
    np.testing.assert_array_equal(top.asnumpy(),
                                  -np.sort(-a, axis=1)[:, :2])


def test_clip_where_maximum():
    a = np.linspace(-3, 3, 12, dtype="float32").reshape(3, 4)
    x = mx.nd.array(a)
    np.testing.assert_allclose(mx.nd.clip(x, -1, 1).asnumpy(),
                               np.clip(a, -1, 1))
    cond = mx.nd.array((a > 0).astype("float32"))
    np.testing.assert_allclose(
        mx.nd.where(cond, x, -x).asnumpy(), np.where(a > 0, a, -a))
    np.testing.assert_allclose(mx.nd.maximum(x, 0).asnumpy(),
                               np.maximum(a, 0))


def test_one_hot_and_pick():
    idx = mx.nd.array([0, 2, 1], dtype="float32")
    oh = mx.nd.one_hot(idx, 4)
    np.testing.assert_array_equal(oh.asnumpy(),
                                  np.eye(4, dtype="float32")[[0, 2, 1]])
    data = mx.nd.array(np.arange(12, dtype="float32").reshape(3, 4))
    picked = mx.nd.pick(data, idx, axis=1)
    np.testing.assert_array_equal(picked.asnumpy(), [0, 6, 9])


def test_stack_flip_rot():
    a = np.arange(6, dtype="float32").reshape(2, 3)
    x = mx.nd.array(a)
    st = mx.nd.stack(x, x, axis=1)
    assert st.shape == (2, 2, 3)
    np.testing.assert_array_equal(mx.nd.flip(x, axis=1).asnumpy(),
                                  a[:, ::-1])
    np.testing.assert_array_equal(mx.nd.swapaxes(x, 0, 1).asnumpy(), a.T)


def test_batch_dot_transpose_combos():
    rng = np.random.RandomState(0)
    a = rng.randn(4, 2, 3).astype("float32")
    b = rng.randn(4, 3, 5).astype("float32")
    out = mx.nd.batch_dot(mx.nd.array(a), mx.nd.array(b))
    np.testing.assert_allclose(out.asnumpy(), a @ b, rtol=1e-5)
    out_t = mx.nd.batch_dot(mx.nd.array(a.transpose(0, 2, 1)),
                            mx.nd.array(b), transpose_a=True)
    np.testing.assert_allclose(out_t.asnumpy(), a @ b, rtol=1e-5)
    out_tb = mx.nd.batch_dot(mx.nd.array(a),
                             mx.nd.array(b.transpose(0, 2, 1)),
                             transpose_b=True)
    np.testing.assert_allclose(out_tb.asnumpy(), a @ b, rtol=1e-5)


def test_l2_normalization_and_lrn():
    rng = np.random.RandomState(0)
    a = rng.rand(2, 4).astype("float32") + 0.1
    out = mx.nd.L2Normalization(mx.nd.array(a), mode="instance")
    np.testing.assert_allclose(
        out.asnumpy(), a / np.linalg.norm(a, axis=1, keepdims=True),
        rtol=1e-5)
    x = mx.nd.array(rng.rand(1, 4, 5, 5).astype("float32"))
    lrn = mx.nd.LRN(x, nsize=3)
    assert lrn.shape == x.shape
    assert np.isfinite(lrn.asnumpy()).all()


def test_layernorm_numerics():
    rng = np.random.RandomState(0)
    a = rng.randn(3, 6).astype("float32")
    gamma = np.ones(6, dtype="float32")
    beta = np.zeros(6, dtype="float32")
    out = mx.nd.LayerNorm(mx.nd.array(a), mx.nd.array(gamma),
                          mx.nd.array(beta))
    mu = a.mean(axis=1, keepdims=True)
    sig = a.std(axis=1, keepdims=True)
    np.testing.assert_allclose(out.asnumpy(), (a - mu) / (sig + 1e-5),
                               rtol=1e-3, atol=1e-4)


def test_batchnorm_train_vs_eval():
    rng = np.random.RandomState(0)
    a = rng.randn(8, 3, 4, 4).astype("float32") * 2 + 1
    x = mx.nd.array(a)
    gamma = mx.nd.ones((3,))
    beta = mx.nd.zeros((3,))
    mean = mx.nd.zeros((3,))
    var = mx.nd.ones((3,))
    with mx.autograd.record():  # train mode: batch statistics
        out = mx.nd.BatchNorm(x, gamma, beta, mean, var)
    o = out.asnumpy()
    np.testing.assert_allclose(o.mean(axis=(0, 2, 3)), np.zeros(3),
                               atol=1e-4)
    np.testing.assert_allclose(o.std(axis=(0, 2, 3)), np.ones(3), atol=1e-2)
    # aux moving stats were updated toward batch stats
    assert abs(float(mean.asnumpy().mean())) > 1e-3
    # eval mode: uses (updated) moving stats, not batch stats
    out_eval = mx.nd.BatchNorm(x, gamma, beta, mean, var)
    assert abs(out_eval.asnumpy().mean()) > 1e-3


def test_dropout_statistics():
    mx.random.seed(7)
    x = mx.nd.ones((1000,))
    with mx.autograd.record():
        out = mx.nd.Dropout(x, p=0.3)
    o = out.asnumpy()
    kept = (o > 0).mean()
    assert 0.6 < kept < 0.8                      # ~70% kept
    np.testing.assert_allclose(o[o > 0][0], 1 / 0.7, rtol=1e-5)
    # eval mode: identity
    np.testing.assert_array_equal(mx.nd.Dropout(x, p=0.3).asnumpy(),
                                  np.ones(1000, dtype="float32"))


def test_broadcast_like_and_expand():
    a = mx.nd.array([[1.0], [2.0]])
    b = mx.nd.zeros((2, 3))
    out = mx.nd.broadcast_like(a, b)
    assert out.shape == (2, 3)
    np.testing.assert_array_equal(out.asnumpy()[0], [1, 1, 1])
    np.testing.assert_array_equal(
        mx.nd.broadcast_to(a, shape=(2, 4)).asnumpy()[1], [2, 2, 2, 2])


def test_unary_gradients_numeric():
    """Finite-difference check over a basket of unary ops (the reference's
    check_numeric_gradient pattern)."""
    for opname in ("tanh", "sigmoid", "exp", "sqrt", "square"):
        data = mx.sym.Variable("data")
        out = mx.sym.sum(getattr(mx.sym, opname)(data))
        loc = {"data": np.random.RandomState(0).rand(4, 3).astype("float32")
               + 0.5}
        tu.check_numeric_gradient(out, loc, rtol=0.08, atol=1e-2)


def test_take_modes():
    a = np.arange(12, dtype="float32").reshape(4, 3)
    idx = mx.nd.array([1, 5], dtype="float32")  # 5 out of range
    out = mx.nd.take(mx.nd.array(a), idx, mode="clip")
    np.testing.assert_array_equal(out.asnumpy(), a[[1, 3]])
    out_wrap = mx.nd.take(mx.nd.array(a), idx, mode="wrap")
    np.testing.assert_array_equal(out_wrap.asnumpy(), a[[1, 1]])


def test_scatter_and_gather_nd():
    idx = mx.nd.array([[0, 1], [1, 0]], dtype="float32")
    data = mx.nd.array(np.arange(4, dtype="float32").reshape(2, 2))
    g = mx.nd.gather_nd(data, idx)
    np.testing.assert_array_equal(g.asnumpy(), [1, 2])
    s = mx.nd.scatter_nd(mx.nd.array([9.0, 8.0]), idx, shape=(2, 2))
    np.testing.assert_array_equal(s.asnumpy(), [[0, 9], [8, 0]])


def test_sync_batch_norm_matches_batch_norm_single_device():
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(4, 3, 5, 5).astype(np.float32))
    gamma = mx.nd.array(rng.rand(3).astype(np.float32) + 0.5)
    beta = mx.nd.array(rng.randn(3).astype(np.float32))
    mm, mv = mx.nd.zeros((3,)), mx.nd.ones((3,))
    with mx.autograd.record():
        a = mx.nd.BatchNorm(x, gamma, beta, mm.copy(), mv.copy(),
                            fix_gamma=False, eps=1e-5)
        b = mx.nd.contrib.SyncBatchNorm(x, gamma, beta, mm.copy(), mv.copy(),
                                        eps=1e-5, ndev=1)
    np.testing.assert_allclose(a.asnumpy(), b.asnumpy(), rtol=1e-5, atol=1e-6)
    # eval mode normalizes with the moving stats
    c = mx.nd.contrib.SyncBatchNorm(x, gamma, beta, mm, mv, eps=1e-5)
    assert np.isfinite(c.asnumpy()).all()


def test_sync_batch_norm_shard_map_moments_are_global():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.ops.nn import batch_norm, sync_batch_norm
    from jax import shard_map

    rng = np.random.RandomState(0)
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    X = rng.randn(8, 3, 4, 4).astype(np.float32)
    G, B = np.ones(3, np.float32), np.zeros(3, np.float32)

    def local_bn(xs):
        out, _m, _v = sync_batch_norm(xs, jnp.asarray(G), jnp.asarray(B),
                                      jnp.zeros(3), jnp.ones(3), eps=1e-5,
                                      __training__=True)
        return out

    f = shard_map(local_bn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    got = np.asarray(f(jnp.asarray(X)))
    want, _, _ = batch_norm(jnp.asarray(X), jnp.asarray(G), jnp.asarray(B),
                            jnp.zeros(3), jnp.ones(3), eps=1e-5,
                            fix_gamma=False, __training__=True)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)


def test_batchnorm_training_variance_large_mean():
    """Single-pass BN stats must not catastrophically cancel when
    |mean| >> std (r4 / ADVICE r3: raw E[x^2]-E[x]^2 in f32 yields var~0
    for mean~1e4, std~1; the shifted-pivot form restores precision)."""
    rng = np.random.RandomState(0)
    x = (1e4 + rng.randn(8, 4, 16, 16)).astype("float32")
    with mx.autograd.record():
        out = mx.nd.BatchNorm(
            mx.nd.array(x), mx.nd.ones(4), mx.nd.zeros(4),
            mx.nd.zeros(4), mx.nd.ones(4), fix_gamma=False)
    true_var = x.var(axis=(0, 2, 3))
    got = out.asnumpy()
    expect = (x - x.mean(axis=(0, 2, 3), keepdims=True).reshape(1, 4, 1, 1)) \
        / np.sqrt(true_var.reshape(1, 4, 1, 1) + 1e-3)
    assert np.allclose(got, expect, atol=2e-2), \
        (np.abs(got - expect).max(), true_var)
