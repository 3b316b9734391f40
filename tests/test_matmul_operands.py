"""GELU's result and a dropout's keep-mask are values under differentiation
(``ops/elemwise.py::as_value``): the numbers are the plain formula's on
every path that differentiates, and the mask a key draws is the mask it
always drew (``perf/reference/bert.py`` follows the step's key chain)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import random as mx_random
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import registry

RATE, UNITS, HIDDEN, ROWS, SEQ = 0.1, 16, 64, 4, 8
SEED = 29


class FFN(gluon.HybridBlock):
    """BERT's feed-forward half: Dense -> GELU -> Dense -> Dropout ->
    residual + LayerNorm."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn_1 = nn.Dense(HIDDEN, flatten=False, prefix="ffn1_")
            self.act = nn.GELU()
            self.ffn_2 = nn.Dense(UNITS, flatten=False, prefix="ffn2_")
            self.drop = nn.Dropout(RATE)
            self.norm = nn.LayerNorm()

    def hybrid_forward(self, F, x):
        return self.norm(self.drop(self.ffn_2(self.act(self.ffn_1(x)))) + x)


def _plain_loss(w, x, target, key):
    """The same arithmetic in ``jax.numpy``, nothing from ``mxnet_tpu``."""
    pre = x @ w["ffn1_weight"].T + w["ffn1_bias"]
    h = 0.5 * pre * (1.0 + jax.lax.erf(pre / jnp.sqrt(2.0)))
    f = h @ w["ffn2_weight"].T + w["ffn2_bias"]
    keep = 1.0 - RATE
    f = f * jax.random.bernoulli(key, keep, f.shape).astype(f.dtype) / keep
    y = f + x
    mean = y.mean(-1, keepdims=True)
    var = ((y - mean) ** 2).mean(-1, keepdims=True)
    y = (y - mean) / jnp.sqrt(var + 1e-5) * w["layernorm0_gamma"] \
        + w["layernorm0_beta"]
    return ((y - target) ** 2).mean()


def _net_and_data():
    mx_random.seed(SEED)
    net = FFN(prefix="ffn_")
    net.initialize(mx.init.Normal(0.3))
    rng = np.random.RandomState(SEED)
    x = rng.randn(ROWS, SEQ, UNITS).astype("float32")
    target = rng.randn(ROWS, SEQ, UNITS).astype("float32")
    net(mx.nd.array(x))                 # shapes; predict mode draws no mask
    weights = {name[len("ffn_"):]: jnp.asarray(p.data().asnumpy())
               for name, p in net.collect_params().items()}
    return net, x, target, weights


def _first_key():
    """The key the next stochastic call draws from the global stream."""
    mx_random.seed(SEED + 1)
    key = jnp.asarray(mx_random.next_key())
    mx_random.seed(SEED + 1)
    return key


def _run_tape(net, x, target):
    """Eager ops on the autograd tape; the Dropout op takes the stream's
    key itself."""
    key = _first_key()
    with autograd.record():
        loss = ((net(mx.nd.array(x)) - mx.nd.array(target)) ** 2).mean()
    loss.backward()
    grads = {name[len("ffn_"):]: p.grad().asnumpy()
             for name, p in net.collect_params().items()}
    return float(loss.asscalar()), grads, key


def _run_hybrid(net, x, target):
    """A CachedOp graph: the hybridized block's call draws one key and
    opens a scope on it; its children are part of its program and open
    none, so the Dropout op takes the scope's first key, as in
    ``SPMDTrainer``'s step."""
    net.hybridize()
    loss, grads, key = _run_tape(net, x, target)
    return loss, grads, jax.random.split(key)[1]


def _run_spmd(net, x, target):
    """``SPMDTrainer``'s jitted step with plain SGD at rate 1: the
    gradient is what the step took off each parameter."""
    from mxnet_tpu.parallel import (FunctionalOptimizer, SPMDTrainer,
                                    device_mesh)
    before = {name: p.data().asnumpy()
              for name, p in net.collect_params().items()}
    trainer = SPMDTrainer(
        net, lambda out, label: ((out - label) ** 2).mean(),
        FunctionalOptimizer("sgd", 1.0),
        device_mesh({"pp": 1, "dp": 1, "sp": 1, "tp": 1},
                    devices=jax.devices()[:1]), donate=False)
    key = _first_key()
    loss = trainer.step(x, target)
    trainer.sync_to_block()
    grads = {name[len("ffn_"):]: before[name] - p.data().asnumpy()
             for name, p in net.collect_params().items()}
    return float(loss), grads, jax.random.split(key)[1]


@pytest.mark.parametrize("run", [_run_tape, _run_hybrid, _run_spmd],
                         ids=["tape", "hybridize", "spmd_step"])
def test_ffn_half_follows_the_plain_formula(run):
    net, x, target, weights = _net_and_data()
    loss, grads, key = run(net, x, target)
    want_loss, want = jax.value_and_grad(_plain_loss)(
        weights, jnp.asarray(x), jnp.asarray(target), key)
    assert abs(loss - float(want_loss)) < 1e-6
    assert sorted(grads) == sorted(want)
    for name, g in want.items():
        np.testing.assert_allclose(grads[name], np.asarray(g), rtol=0,
                                   atol=1e-6, err_msg=name)


def _dropout_plain(fn, key, ones):
    return fn(key, ones, p=RATE, __training__=True)


def _dropout_differentiated(fn, key, ones):
    out, pull = jax.vjp(
        lambda d: fn(key, d, p=RATE, __training__=True), ones)
    # the cotangent passes the same mask
    np.testing.assert_array_equal(np.asarray(pull(ones)[0]),
                                  np.asarray(out))
    return out


def _dropout_in_a_jitted_grad(fn, key, ones):
    def scaled(scale, k):
        out = fn(k, ones * scale, p=RATE, __training__=True)
        return out.sum(), out
    return jax.jit(jax.grad(scaled, has_aux=True))(jnp.float32(1.0), key)[1]


@pytest.mark.parametrize("call", [_dropout_plain, _dropout_differentiated,
                                  _dropout_in_a_jitted_grad],
                         ids=["plain", "vjp", "jit_grad"])
@pytest.mark.parametrize("axes", [None, (1,)], ids=["full", "axes1"])
def test_keep_mask_is_the_keys_bernoulli_bit_for_bit(call, axes):
    """Whatever the trace, element (i, j, k) is kept exactly where
    ``jax.random.bernoulli(key, 1 - rate, shape)`` says, as before PR 29
    and as the benchmark's reference draws it."""
    fn = registry.get("Dropout").fn
    if axes is not None:
        fn = jax.tree_util.Partial(fn, axes=axes)
    key = jax.random.PRNGKey(2900000017)
    shape = (3, 8, 128)
    out = np.asarray(call(fn, key, jnp.ones(shape, jnp.float32)))
    drawn = [1 if axes and a in axes else n for a, n in enumerate(shape)]
    kept = np.broadcast_to(np.asarray(
        jax.random.bernoulli(key, 1.0 - RATE, tuple(drawn))), shape)
    np.testing.assert_array_equal(out != 0, kept)
    np.testing.assert_array_equal(
        out[kept], np.full(kept.sum(), np.float32(1.0) / np.float32(0.9)))


def _operand_counts(fn):
    """``matmul.operand`` counts by (op, kind) made while ``fn`` runs."""
    from mxnet_tpu.test_utils import counted
    part = lambda label, name: label.split(name + '="')[1].split('"')[0]
    return {(part(label, "op"), part(label, "kind")): n
            for label, n in counted("matmul.operand", fn).items()}


def test_operand_counter_says_value_only_under_differentiation():
    gelu = registry.get("LeakyReLU").fn
    drop = registry.get("Dropout").fn
    x = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    key = jax.random.PRNGKey(3)

    def forward_only():
        gelu(x, act_type="gelu")
        drop(key, x, p=RATE, __training__=True)
        drop(key, x, p=RATE)                    # predict mode: no mask
    assert _operand_counts(forward_only) == {
        ("gelu", "recipe"): 1, ("dropout", "recipe"): 1}

    def differentiated():
        jax.grad(lambda v: drop(key, gelu(v, act_type="gelu"), p=RATE,
                                __training__=True).sum())(x)
    assert _operand_counts(differentiated) == {
        ("gelu", "value"): 1, ("dropout", "value"): 1}


def test_spmd_step_traces_values_and_no_recipe():
    """``SPMDTrainer``'s step is one differentiated trace: every GELU and
    every training-mode dropout in it is a value."""
    net, x, target, _ = _net_and_data()
    assert _operand_counts(lambda: _run_spmd(net, x, target)) == {
        ("gelu", "value"): 1, ("dropout", "value"): 1}


def test_gelu_value_rule_keeps_the_formulas_gradient():
    """The rule's backward is the plain formula's own, to the last bit, and
    differentiates again (``autograd.grad(create_graph=True)``)."""
    gelu = registry.get("LeakyReLU").fn
    x = jnp.linspace(-4.0, 4.0, 101, dtype=jnp.float32)
    plain = lambda v: 0.5 * v * (1.0 + jax.lax.erf(v / (2.0 ** 0.5)))
    rule = lambda v: gelu(v, act_type="gelu")
    np.testing.assert_array_equal(np.asarray(rule(x)), np.asarray(plain(x)))
    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda v: rule(v).sum())(x)),
        np.asarray(jax.grad(lambda v: plain(v).sum())(x)))
    second = jax.grad(lambda v: jax.grad(lambda u: rule(u).sum())(v).sum())
    want = jax.grad(lambda v: jax.grad(lambda u: plain(u).sum())(v).sum())
    np.testing.assert_allclose(np.asarray(second(x)), np.asarray(want(x)),
                               rtol=0, atol=1e-6)
