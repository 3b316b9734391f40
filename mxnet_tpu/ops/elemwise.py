"""Elementwise unary/binary/scalar operators.

Reference being rebuilt: ``src/operator/tensor/elemwise_unary_op_basic.cc``,
``elemwise_binary_op_basic.cc``, ``elemwise_binary_scalar_op_*.cc`` and the
scalar functor zoo ``src/operator/mshadow_op.h``.  Each op here is one pure
JAX function; XLA fuses chains of them into single TPU kernels, which is why
there is no hand-written kernel layer (the mshadow expression templates'
entire job is done by the compiler).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import np_dtype, parse_bool, parse_float
from ..telemetry import bus as _tel
from .registry import register


def _unary(name, jfn, aliases=()):
    def fn(x):
        return jfn(x)
    fn.__name__ = name
    fn.__doc__ = f"Elementwise {name} (reference src/operator/tensor/elemwise_unary_op_basic.cc / mshadow_op.h)."
    register(name, aliases=aliases)(fn)
    return fn


_unary("abs", jnp.abs)
_unary("sign", jnp.sign)
# MXNet round: ties away from zero (mshadow_op::round), NOT banker's
_unary("round", lambda x: jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5))
_unary("rint", jnp.rint)
_unary("ceil", jnp.ceil)
_unary("floor", jnp.floor)
_unary("trunc", jnp.trunc)
_unary("fix", jnp.trunc)
_unary("square", jnp.square)
_unary("sqrt", jnp.sqrt)
_unary("rsqrt", lambda x: jax.lax.rsqrt(x))
_unary("cbrt", jnp.cbrt)
_unary("rcbrt", lambda x: 1.0 / jnp.cbrt(x))
_unary("exp", jnp.exp)
_unary("log", jnp.log)
_unary("log10", jnp.log10)
_unary("log2", jnp.log2)
_unary("log1p", jnp.log1p)
_unary("expm1", jnp.expm1)
_unary("sin", jnp.sin)
_unary("cos", jnp.cos)
_unary("tan", jnp.tan)
_unary("arcsin", jnp.arcsin)
_unary("arccos", jnp.arccos)
_unary("arctan", jnp.arctan)
_unary("sinh", jnp.sinh)
_unary("cosh", jnp.cosh)
_unary("tanh", jnp.tanh)
_unary("arcsinh", jnp.arcsinh)
_unary("arccosh", jnp.arccosh)
_unary("arctanh", jnp.arctanh)
_unary("degrees", jnp.degrees)
_unary("radians", jnp.radians)
_unary("negative", jnp.negative, aliases=("_np_negative",))
_unary("reciprocal", lambda x: 1.0 / x)
_unary("sigmoid", jax.nn.sigmoid)
_unary("softsign", jax.nn.soft_sign)
_unary("relu", jax.nn.relu)
_unary("erf", jax.scipy.special.erf)
_unary("erfinv", jax.scipy.special.erfinv)
_unary("gammaln", jax.scipy.special.gammaln)
# the Γ function itself (reference elemwise_unary_op_basic.cc:1290 —
# distinct from the _random_gamma sampler; true Γ, not exp(lnΓ) = |Γ|)
_unary("gamma", jax.scipy.special.gamma)
_unary("logical_not", lambda x: (x == 0).astype(x.dtype))
_unary("isnan", jnp.isnan)
_unary("isinf", jnp.isinf)
_unary("isfinite", jnp.isfinite)
_unary("size_array", lambda x: jnp.asarray([x.size], dtype=jnp.int32))  # int64 truncates on 32-bit jax anyway
_unary("shape_array", lambda x: jnp.asarray(x.shape, dtype=jnp.int32))


@register("_copy", aliases=("identity",))
def _copy(x):
    """Identity copy (reference ``_copy`` op)."""
    return jnp.asarray(x)


@register("_copyto")
def _copyto(x):
    """Reference ``_copyto`` (ndarray.cc CopyFromTo): cross-device copy.

    Device placement is handled by the NDArray frontend / XLA runtime; the op
    itself is an identity at the array level.
    """
    return jnp.asarray(x)


@register("BlockGrad", aliases=("stop_gradient",))
def block_grad(x):
    """Stops gradient flow (reference ``BlockGrad``,
    src/operator/tensor/elemwise_unary_op_basic.cc)."""
    return jax.lax.stop_gradient(x)


@register("make_loss")
def make_loss(x):
    """Head-gradient source (reference ``make_loss`` / ``MakeLoss``):
    forward identity; gradient of the output w.r.t. input is all-ones
    regardless of the incoming cotangent."""
    @jax.custom_vjp
    def _f(v):
        return v

    def _fwd(v):
        return v, None

    def _bwd(res, g):
        return (jnp.ones_like(g),)

    _f.defvjp(_fwd, _bwd)
    return _f(x)


@register("clip")
def clip(x, a_min=None, a_max=None):
    """Reference ``clip`` (src/operator/tensor/matrix_op.cc); gradient is zero
    outside the clip range, matching the reference's backward."""
    return jnp.clip(x, parse_float(a_min), parse_float(a_max))


def as_value(x, op):
    """``x`` as a value that exists once in device memory, for the result
    of an expensive elementwise recipe that matmuls consume (``op`` names
    it for the ``matmul.operand`` counter).

    XLA's fusion pass clones an elementwise producer into each of its
    consumers; inside a convolution fusion the clone is run again for
    every output tile that needs its operand tile, on the vector unit.
    That is free for a cast or a LayerNorm apply and costs three to nine
    passes for an ``erf`` or the threefry rounds of a dropout mask (BERT's
    ``ffn2`` weight gradient: four times its twin ``ffn1``'s, PERF.md,
    PR 29).  Behind the barrier the recipe is one fusion of its own, and
    forward and backward consumers read what it wrote; it may still ride
    as the epilogue of the matmul that PRODUCES its input.  Only a
    differentiation rule calls this: an undifferentiated trace keeps the
    recipe XLA may fuse as it likes, and an eager result is a value
    already."""
    _tel.count("matmul.operand", kind="value", op=op)
    return jax.lax.optimization_barrier(x)


def _gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / (2.0 ** 0.5)))


@jax.custom_vjp
def gelu(x):
    """Exact (erf) GELU, ``x * Phi(x)``.  Under differentiation its result
    is a value (``as_value``): the matmul it feeds and that matmul's weight
    gradient read it.  The derivative stays a recipe: it multiplies the
    cotangent a matmul produced, once an element."""
    _tel.count("matmul.operand", kind="recipe", op="gelu")
    return _gelu_erf(x)


def _gelu_fwd(x):
    return as_value(_gelu_erf(x), "gelu"), x


def _gelu_bwd(x, g):
    _, pull = jax.vjp(_gelu_erf, x)
    return pull(g)


gelu.defvjp(_gelu_fwd, _gelu_bwd)


@register("LeakyReLU")
def leaky_relu(x, *args, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334):
    """Reference ``LeakyReLU`` (src/operator/leaky_relu.cc): leaky/elu/prelu/
    selu/gelu variants.  ``prelu`` takes gamma as a second input."""
    slope = parse_float(slope, 0.25)
    if act_type == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if act_type == "gelu":
        return gelu(x)
    if act_type == "prelu":
        gamma = args[0]
        gamma = jnp.reshape(gamma, (1, -1) + (1,) * (x.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(x > 0, x, gamma * x)
    if act_type == "rrelu":
        slope = (parse_float(lower_bound, 0.125) + parse_float(upper_bound, 0.334)) / 2
        return jnp.where(x > 0, x, slope * x)
    raise ValueError(f"unknown LeakyReLU act_type {act_type}")


@register("Activation")
def activation(x, act_type="relu"):
    """Reference ``Activation`` (src/operator/nn/activation.cc)."""
    if act_type == "relu":
        return jax.nn.relu(x)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    raise ValueError(f"unknown act_type {act_type}")


@register("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(parse_float(alpha, 0.2) * x + parse_float(beta, 0.5), 0, 1)


@register("softplus")
def softplus(x):
    return jax.nn.softplus(x)


# ---------------------------------------------------------------------------
# Elementwise binary (same-shape) — reference elemwise_binary_op_basic.cc.
# The broadcast_* family (mx's general case) lives in broadcast_reduce.py;
# these are registered separately to keep name parity.
# ---------------------------------------------------------------------------
def _binary(name, jfn, aliases=()):
    def fn(lhs, rhs):
        return jfn(lhs, rhs)
    fn.__name__ = name
    register(name, aliases=aliases)(fn)
    return fn


_binary("elemwise_add", jnp.add, aliases=("_plus", "_add"))
_binary("elemwise_sub", jnp.subtract, aliases=("_minus", "_sub"))
_binary("elemwise_mul", jnp.multiply, aliases=("_mul",))
_binary("elemwise_div", jnp.divide, aliases=("_div",))
# ties: full cotangent to the LHS (reference mshadow_op ge/le backward);
# jnp.maximum's VJP would split 50/50
_binary("_maximum", lambda a, b: jnp.where(a >= b, a, b))
_binary("_minimum", lambda a, b: jnp.where(a <= b, a, b))
_binary("_hypot", jnp.hypot)
_binary("_power", jnp.power, aliases=("_Power",))
_binary("_mod", jnp.mod)
# Same-shape comparison/logic ops (reference elemwise_binary_op_logic.cc:
# `_equal` etc. are the non-broadcast tensor-tensor variants behind
# `nd.equal(a, b)`); outputs are 0/1 in the input dtype.
_binary("_equal", lambda a, b: (a == b).astype(a.dtype))
_binary("_not_equal", lambda a, b: (a != b).astype(a.dtype))
_binary("_greater", lambda a, b: (a > b).astype(a.dtype))
_binary("_greater_equal", lambda a, b: (a >= b).astype(a.dtype))
_binary("_lesser", lambda a, b: (a < b).astype(a.dtype))
_binary("_lesser_equal", lambda a, b: (a <= b).astype(a.dtype))
_binary("_logical_and", lambda a, b: ((a != 0) & (b != 0)).astype(a.dtype))
_binary("_logical_or", lambda a, b: ((a != 0) | (b != 0)).astype(a.dtype))
_binary("_logical_xor", lambda a, b: ((a != 0) ^ (b != 0)).astype(a.dtype))
# `_grad_add` (elemwise_binary_op_basic.cc): plain add used by the reference's
# gradient-aggregation pass; here autodiff aggregates for us but the op name
# stays callable.
_binary("_grad_add", jnp.add)
# `_scatter_elemwise_div` (elemwise_scatter_op.cc): divide, writing only the
# lhs' stored values — identical to division on the dense compat layer.
_binary("_scatter_elemwise_div", jnp.divide)


@register("add_n", wrap_list=True, aliases=("ElementWiseSum", "_sum"))
def add_n(*args):
    """Sum of N arrays (reference ``add_n``/``ElementWiseSum``,
    src/operator/tensor/elemwise_sum.cc)."""
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


# ---------------------------------------------------------------------------
# Scalar ops — reference elemwise_binary_scalar_op_*.cc.  ``scalar`` is kept a
# *traced* argument would cause recompiles in jit caches keyed on attrs; since
# eager execution doesn't jit per-op, a plain Python float is fine and jit
# users (CachedOp) bake the scalar into the compiled graph exactly like the
# reference bakes it into the op node.
# ---------------------------------------------------------------------------
def _scalar(name, jfn):
    def fn(x, scalar=1.0):
        return jfn(x, parse_float(scalar, 1.0))
    fn.__name__ = name
    register(name)(fn)
    return fn


_scalar("_plus_scalar", lambda x, s: x + jnp.asarray(s, x.dtype))
_scalar("_minus_scalar", lambda x, s: x - jnp.asarray(s, x.dtype))
_scalar("_rminus_scalar", lambda x, s: jnp.asarray(s, x.dtype) - x)
_scalar("_mul_scalar", lambda x, s: x * jnp.asarray(s, x.dtype))
_scalar("_div_scalar", lambda x, s: x / jnp.asarray(s, x.dtype))
_scalar("_rdiv_scalar", lambda x, s: jnp.asarray(s, x.dtype) / x)
_scalar("_mod_scalar", lambda x, s: jnp.mod(x, jnp.asarray(s, x.dtype)))
_scalar("_rmod_scalar", lambda x, s: jnp.mod(jnp.asarray(s, x.dtype), x))
_scalar("_power_scalar", lambda x, s: jnp.power(x, jnp.asarray(s, x.dtype)))
_scalar("_rpower_scalar", lambda x, s: jnp.power(jnp.asarray(s, x.dtype), x))
# ties: full cotangent to the tensor operand (reference ge/le backward;
# see _maximum/_minimum above)
_scalar("_maximum_scalar", lambda x, s: jnp.where(x >= jnp.asarray(s, x.dtype), x, jnp.asarray(s, x.dtype)))
_scalar("_minimum_scalar", lambda x, s: jnp.where(x <= jnp.asarray(s, x.dtype), x, jnp.asarray(s, x.dtype)))
_scalar("_hypot_scalar", lambda x, s: jnp.hypot(x, jnp.asarray(s, x.dtype)))
_scalar("_equal_scalar", lambda x, s: (x == s).astype(x.dtype))
_scalar("_not_equal_scalar", lambda x, s: (x != s).astype(x.dtype))
_scalar("_greater_scalar", lambda x, s: (x > s).astype(x.dtype))
_scalar("_greater_equal_scalar", lambda x, s: (x >= s).astype(x.dtype))
_scalar("_lesser_scalar", lambda x, s: (x < s).astype(x.dtype))
_scalar("_lesser_equal_scalar", lambda x, s: (x <= s).astype(x.dtype))
_scalar("_logical_and_scalar", lambda x, s: ((x != 0) & (s != 0)).astype(x.dtype))
_scalar("_logical_or_scalar", lambda x, s: ((x != 0) | (s != 0)).astype(x.dtype))
_scalar("_logical_xor_scalar", lambda x, s: ((x != 0) ^ (s != 0)).astype(x.dtype))
# `_scatter_*` scalar ops (elemwise_scatter_op.cc) touch only stored values on
# sparse inputs; on the dense-backed sparse compat layer they coincide with the
# plain scalar ops.
_scalar("_scatter_plus_scalar", lambda x, s: x + jnp.asarray(s, x.dtype))
_scalar("_scatter_minus_scalar", lambda x, s: x - jnp.asarray(s, x.dtype))
_scalar("smooth_l1", lambda x, s: jnp.where(jnp.abs(x) < 1.0 / (s * s),
                                            0.5 * s * s * x * x,
                                            jnp.abs(x) - 0.5 / (s * s)))


@register("cast", aliases=("Cast", "amp_cast"))
def cast(x, dtype="float32"):
    """Reference ``Cast`` (elemwise_unary_op_basic.cc) and ``amp_cast``
    (src/operator/tensor/amp_cast.cc).

    int64/uint64 casts run as int32/uint32 — the documented PARITY scope
    decision for this x64-disabled TPU build (the mapping is explicit here
    so it is policy, not a silent jax truncation warning).
    """
    from ..base import np_dtype
    dt = _np.dtype(np_dtype(dtype))
    if dt == _np.int64:
        dt = _np.dtype(_np.int32)
    elif dt == _np.uint64:
        dt = _np.dtype(_np.uint32)
    return x.astype(dt)


@register("amp_multicast", wrap_list=True)
def amp_multicast(*args, num_outputs=None, cast_narrow=False):
    """Reference ``amp_multicast``: cast all inputs to the widest (or
    narrowest) dtype among them."""
    dts = [a.dtype for a in args]
    target = jnp.result_type(*dts) if not parse_bool(cast_narrow) else min(
        dts, key=lambda d: jnp.finfo(d).bits if jnp.issubdtype(d, jnp.floating) else 64)
    return tuple(a.astype(target) for a in args)


@register("where")
def where(condition, x, y):
    """Reference ``where`` (src/operator/tensor/control_flow_op.cc):
    elementwise select, or — when ``condition`` is 1-D and x/y are not —
    per-row select along the first axis."""
    cond = condition.astype(bool)
    if cond.ndim == 1 and x.ndim > 1:
        cond = cond.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(cond, x, y)


@register("zeros_like")
def zeros_like(x):
    return jnp.zeros_like(x)


@register("ones_like")
def ones_like(x):
    return jnp.ones_like(x)


@register("amp_cast")
def amp_cast(data, dtype=None):
    """AMP-inserted cast (reference ``src/operator/tensor/amp_cast.cc``):
    identity up to dtype — the low-precision pass (contrib.amp
    convert_symbol) inserts these around listed ops; XLA folds them into
    the neighboring matmul/conv."""
    return data.astype(np_dtype(dtype))


@register("amp_multicast")
def amp_multicast(*data, num_outputs=None):
    """Cast all inputs to the widest of their dtypes (reference
    ``amp_cast.cc AMPMultiCast``)."""
    dt = jnp.result_type(*[d.dtype for d in data])
    return tuple(d.astype(dt) for d in data)


@register("_contrib_bitwise_and", aliases=("bitwise_and",))
def bitwise_and(a, b):
    return jnp.bitwise_and(a.astype(jnp.int32), b.astype(jnp.int32))


@register("_contrib_bitwise_or", aliases=("bitwise_or",))
def bitwise_or(a, b):
    return jnp.bitwise_or(a.astype(jnp.int32), b.astype(jnp.int32))


@register("_contrib_bitwise_xor", aliases=("bitwise_xor",))
def bitwise_xor(a, b):
    return jnp.bitwise_xor(a.astype(jnp.int32), b.astype(jnp.int32))


@register("digamma")
def digamma(a):
    import jax.scipy.special as jsp
    return jsp.digamma(a)
