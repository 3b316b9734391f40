"""Neural-network operators: FullyConnected, Convolution, Pooling, norms,
softmax family, dropout, RNN.

Reference being rebuilt: ``src/operator/nn/`` (27.9k LoC of CPU/cuDNN/MKL-DNN
kernels — fully_connected.cc, convolution.cc, pooling.cc, batch_norm.cc,
layer_norm.cc, softmax.cc, dropout.cc) and the fused RNN op
(``src/operator/rnn.cc:636``).

TPU-native redesign notes:
- One pure-JAX definition per op; XLA supplies the kernels for every backend
  (the cuDNN/MKL-DNN split disappears).
- Convolutions keep MXNet's NCHW calling convention but are computed via
  ``lax.conv_general_dilated``; XLA relayouts for the MXU.
- The fused RNN op is a ``lax.scan`` over time — the compiler pipelines the
  per-step matmuls; no hand-fused kernel needed.
- Dropout and other stochastic ops take an explicit PRNG key as their first
  array input (JAX-native); the frontend supplies it from the global seed
  state (``mxnet_tpu/random.py``), keeping the MXNet call signature.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import parse_bool, parse_float, parse_int, parse_tuple
from ..telemetry import bus as _tel
from .elemwise import as_value
from .registry import register


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------
@register("FullyConnected")
def fully_connected(data, weight, *bias, num_hidden=None, no_bias=False, flatten=True):
    """Reference ``FullyConnected`` (src/operator/nn/fully_connected.cc):
    ``y = x · Wᵀ + b`` with weight layout (num_hidden, in_dim)."""
    if parse_bool(flatten, True):
        x = jnp.reshape(data, (data.shape[0], -1))
    else:
        x = data
    y = jnp.matmul(x, jnp.transpose(weight))
    if not parse_bool(no_bias) and bias:
        y = y + bias[0]
    return y


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------
def _conv_dims(kernel):
    return len(parse_tuple(kernel))


def _spec(nd, layout=None):
    """Conv dimension-number spec for an MXNet layout string.

    Default is the reference's channel-first convention (NCHW/OIHW,
    src/operator/nn/convolution.cc param ``layout``).  Channel-last layouts
    (NWC/NHWC/NDHWC) are first-class on TPU: the channel dim maps onto the
    MXU/VPU 128-lane minor axis, so the whole conv stack runs without the
    per-op relayout copies XLA inserts for channel-first graphs.  Weight
    layout follows the reference convention for each data layout: the 'N'
    position holds O (num_filter) and the 'C' position holds I (in/group).
    """
    if layout in (None, "None", ""):
        if nd == 1:
            return ("NCH", "OIH", "NCH")
        if nd == 2:
            return ("NCHW", "OIHW", "NCHW")
        return ("NCDHW", "OIDHW", "NCDHW")
    lay = str(layout)
    if len(lay) != nd + 2 or "N" not in lay or "C" not in lay:
        raise ValueError(f"bad conv layout {layout!r} for {nd}-d kernel")
    kern = lay.replace("N", "O").replace("C", "I")
    return (lay, kern, lay)


def _channel_pos(layout, ndim):
    """Channel-dim index for an MXNet layout string (default: axis 1)."""
    if layout in (None, "None", ""):
        return 1
    pos = str(layout).find("C")
    if pos < 0:
        raise ValueError(f"layout {layout!r} has no channel dim 'C'")
    return pos


@register("Convolution")
def convolution(data, weight, *bias, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, workspace=1024,
                no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None):
    """Reference ``Convolution`` (src/operator/nn/convolution.cc).  Grouped
    and depthwise convs map to ``feature_group_count``; the MXU does the rest."""
    nd = _conv_dims(kernel)
    stride = parse_tuple(stride, nd, default=(1,) * nd)
    dilate = parse_tuple(dilate, nd, default=(1,) * nd)
    pad_ = parse_tuple(pad, nd, default=(0,) * nd)
    groups = parse_int(num_group, 1)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _spec(nd, layout))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad_],
        lhs_dilation=(1,) * nd,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=jnp.float32 if data.dtype == jnp.float32 else None,
    )
    if not parse_bool(no_bias) and bias:
        b = bias[0]
        bshape = [1] * out.ndim
        bshape[_channel_pos(layout, out.ndim)] = b.shape[0]
        out = out + jnp.reshape(b, bshape)
    return out


@register("Deconvolution")
def deconvolution(data, weight, *bias, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, target_shape=None, num_filter=None,
                  num_group=1, workspace=512, no_bias=True, cudnn_tune=None,
                  cudnn_off=False, layout=None):
    """Reference ``Deconvolution`` (src/operator/nn/deconvolution.cc):
    transposed convolution = conv with lhs dilation."""
    nd = _conv_dims(kernel)
    if layout not in (None, "None", "") and str(layout).find("C") != 1:
        # channel-last: route through the channel-first path (deconv is never
        # a hot op; one transpose pair keeps a single grouped/adj kernel)
        lay = str(layout)
        c = lay.find("C")
        perm = (0, c) + tuple(i for i in range(1, len(lay)) if i != c)
        inv = tuple(sorted(range(len(perm)), key=lambda i: perm[i]))
        out = deconvolution(
            jnp.transpose(data, perm), jnp.transpose(weight, perm), *bias,
            kernel=kernel, stride=stride, dilate=dilate, pad=pad, adj=adj,
            target_shape=target_shape, num_filter=num_filter,
            num_group=num_group, no_bias=no_bias)
        return jnp.transpose(out, inv)
    kern = parse_tuple(kernel, nd)
    stride = parse_tuple(stride, nd, default=(1,) * nd)
    dilate = parse_tuple(dilate, nd, default=(1,) * nd)
    pad_ = parse_tuple(pad, nd, default=(0,) * nd)
    adj_ = parse_tuple(adj, nd, default=(0,) * nd)
    groups = parse_int(num_group, 1)
    # weight layout for deconv in MXNet: (in_c, out_c/g, *kernel)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _spec(nd))
    # transposed conv: flip kernel, swap in/out channels, dilate lhs
    w = jnp.swapaxes(weight, 0, 1)
    if groups > 1:
        ic = data.shape[1]
        w = jnp.reshape(weight, (groups, ic // groups, -1) + weight.shape[2:])
        w = jnp.swapaxes(w, 1, 2)
        w = jnp.reshape(w, (-1, ic // groups) + weight.shape[2:])
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    pads = []
    for i in range(nd):
        k_eff = (kern[i] - 1) * dilate[i]
        lo = k_eff - pad_[i]
        hi = k_eff - pad_[i] + adj_[i]
        pads.append((lo, hi))
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=pads,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    if not parse_bool(no_bias, True) and bias:
        out = out + jnp.reshape(bias[0], (1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------
@register("Pooling")
def pooling(data, kernel=None, pool_type="max", global_pool=False,
            cudnn_off=False, pooling_convention="valid", stride=None,
            pad=None, p_value=2, count_include_pad=True, layout=None):
    """Reference ``Pooling`` (src/operator/nn/pooling.cc) via
    ``lax.reduce_window``.  Channel-last layouts (NWC/NHWC/NDHWC) are
    first-class: the window is built around the layout's spatial positions,
    no transpose."""
    nd = data.ndim - 2
    c_pos = _channel_pos(layout, data.ndim)
    spatial = tuple(i for i in range(1, data.ndim) if i != c_pos)
    if parse_bool(global_pool):
        axes = spatial
        if pool_type == "max":
            out = jnp.max(data, axis=axes, keepdims=True)
        elif pool_type in ("avg", "sum"):
            out = jnp.mean(data, axis=axes, keepdims=True) if pool_type == "avg" \
                else jnp.sum(data, axis=axes, keepdims=True)
        elif pool_type == "lp":
            p = parse_float(p_value, 2)
            out = jnp.power(jnp.sum(jnp.power(jnp.abs(data), p), axis=axes,
                                    keepdims=True), 1.0 / p)
        else:
            raise ValueError(pool_type)
        return out
    kern = parse_tuple(kernel, nd)
    stride_ = parse_tuple(stride, nd, default=(1,) * nd)
    pad_ = parse_tuple(pad, nd, default=(0,) * nd)
    window = [1] * data.ndim
    strides = [1] * data.ndim
    for i, ax in enumerate(spatial):
        window[ax] = kern[i]
        strides[ax] = stride_[i]
    window = tuple(window)
    strides = tuple(strides)
    conv = str(pooling_convention)

    def _pads():
        ps = [(0, 0)] * data.ndim
        for i, ax in enumerate(spatial):
            if conv == "full":
                # ceil division semantics: add extra padding on the high side
                size = data.shape[ax] + 2 * pad_[i]
                rem = (size - kern[i]) % stride_[i]
                extra = (stride_[i] - rem) % stride_[i] if rem else 0
                ps[ax] = (pad_[i], pad_[i] + extra)
            else:
                ps[ax] = (pad_[i], pad_[i])
        return ps

    pads = _pads()
    # NOTE: init values must be plain scalars matching the monoid identity so
    # JAX lowers to the differentiable reduce_window_max/sum primitives (a
    # traced init falls back to the generic reduce_window with no VJP).
    # Padding goes through reduce_window's own padding argument — the pad
    # semantics are "filled with init", which is exactly max/avg pooling's
    # contract — so the padded activation is never materialized in HBM
    # (a jnp.pad of the 112² ResNet stem costs ~0.3ms/step on a v5e).
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max,
                                 window, strides, pads)
    if pool_type in ("avg", "sum"):
        zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
        s = lax.reduce_window(data, zero, lax.add,
                              window, strides, pads)
        if pool_type == "sum":
            return s
        if parse_bool(count_include_pad, True):
            denom = 1.0
            for k in kern:
                denom *= k
            return s / jnp.asarray(denom, data.dtype)
        cnt = lax.reduce_window(jnp.ones_like(data), zero, lax.add,
                                window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        p = parse_float(p_value, 2)
        s = lax.reduce_window(jnp.power(jnp.abs(data), p), 0.0, lax.add,
                              window, strides, pads)
        return jnp.power(s, 1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
@register("BatchNorm")
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               min_calib_range=None, max_calib_range=None, __training__=False):
    """Reference ``BatchNorm`` (src/operator/nn/batch_norm.cc).

    Returns ``(out, batch_mean, batch_var)``; the imperative frontend updates
    the moving statistics in place (the reference op mutates its aux states on
    the engine thread — here the mutation is a functional rebind done by the
    wrapper, see ``ndarray/register.py``).
    """
    ax = parse_int(axis, 1) % data.ndim
    eps_ = parse_float(eps, 1e-3)
    red_axes = tuple(i for i in range(data.ndim) if i != ax)
    training = parse_bool(__training__) and not parse_bool(use_global_stats)
    if training:
        # one fused pass over the activation: E[x-p] and E[(x-p)²] together
        # (jnp.var would re-read the tensor a second time for Σ(x-μ)² —
        # at ResNet-50 scale that second HBM pass is ~2ms/step on a v5e).
        # The per-channel pivot p (first element along the reduce axes)
        # keeps the f32 E[x²]−E[x]² subtraction from cancelling when
        # |mean| ≫ std; variance is shift-invariant so any pivot near the
        # data restores full precision. The subtract fuses into the same
        # HBM pass.
        idx = tuple(slice(None) if i == ax else 0 for i in range(data.ndim))
        pshape = [1] * data.ndim
        pshape[ax] = data.shape[ax]
        pivot32 = lax.stop_gradient(data[idx]).astype(jnp.float32)
        d32 = data.astype(jnp.float32) - jnp.reshape(pivot32, pshape)
        dmean32 = jnp.mean(d32, axis=red_axes)
        dmeansq32 = jnp.mean(d32 * d32, axis=red_axes)
        var32 = jnp.maximum(dmeansq32 - dmean32 * dmean32, 0.0)
        mean = (pivot32 + dmean32).astype(data.dtype)
        var = var32.astype(data.dtype)
    else:
        mean, var = moving_mean, moving_var
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if parse_bool(fix_gamma, True) else gamma
    inv = lax.rsqrt(var.astype(jnp.float32) + eps_).astype(data.dtype)
    out = (data - jnp.reshape(mean, shape).astype(data.dtype)) * \
        jnp.reshape(inv * g.astype(data.dtype), shape) + \
        jnp.reshape(beta, shape).astype(data.dtype)
    return out, lax.stop_gradient(mean), lax.stop_gradient(var)


def _cross_replica_mean(x, axis_name):
    """pmean over a live mesh axis; identity when the axis is not bound
    (eager, plain jit, or a mesh without that axis)."""
    try:
        return lax.pmean(x, axis_name)
    except NameError:
        return x


@register("_contrib_SyncBatchNorm", aliases=("SyncBatchNorm",))
def sync_batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                    momentum=0.9, fix_gamma=False, use_global_stats=False,
                    output_mean_var=False, ndev=1, key=None, axis_name="dp",
                    __training__=False):
    """Cross-device synchronized BatchNorm (reference
    ``src/operator/contrib/sync_batch_norm.cc`` — channel axis fixed at 1).

    The reference syncs per-device moments through a host-side shared-memory
    barrier keyed by ``key``/``ndev``.  TPU-native: inside ``shard_map`` the
    moments are ``lax.pmean``'d over the data mesh axis (``axis_name``); under
    the fused pjit SPMD step — or on one chip — the plain batch moments are
    already global, so the op degrades to exactly ``BatchNorm``.
    """
    eps_ = parse_float(eps, 1e-3)
    red_axes = tuple(i for i in range(data.ndim) if i != 1)
    training = parse_bool(__training__) and not parse_bool(use_global_stats)
    if training:
        # same shifted single-pass moments as batch_norm (E[x²]−E[x]² in
        # f32 cancels when |mean| ≫ std); the pivot is pmean'd so every
        # replica shifts by the identical constant before aggregation.
        idx = tuple(slice(None) if i == 1 else 0 for i in range(data.ndim))
        pshape = [1] * data.ndim
        pshape[1] = data.shape[1]
        pivot32 = _cross_replica_mean(
            lax.stop_gradient(data[idx]).astype(jnp.float32), axis_name)
        d32 = data.astype(jnp.float32) - jnp.reshape(pivot32, pshape)
        dmean32 = _cross_replica_mean(jnp.mean(d32, axis=red_axes),
                                      axis_name)
        dmeansq32 = _cross_replica_mean(jnp.mean(d32 * d32, axis=red_axes),
                                        axis_name)
        var = jnp.maximum(dmeansq32 - dmean32 * dmean32, 0.0) \
            .astype(data.dtype)
        mean = (pivot32 + dmean32).astype(data.dtype)
    else:
        mean, var = moving_mean, moving_var
    shape = [1] * data.ndim
    shape[1] = data.shape[1]
    g = jnp.ones_like(gamma) if parse_bool(fix_gamma, False) else gamma
    inv = lax.rsqrt(var.astype(jnp.float32) + eps_).astype(data.dtype)
    out = (data - jnp.reshape(mean, shape).astype(data.dtype)) * \
        jnp.reshape(inv * g.astype(data.dtype), shape) + \
        jnp.reshape(beta, shape).astype(data.dtype)
    return out, lax.stop_gradient(mean), lax.stop_gradient(var)


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Reference ``LayerNorm`` (src/operator/nn/layer_norm.cc)."""
    ax = parse_int(axis, -1) % data.ndim
    eps_ = parse_float(eps, 1e-5)
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=ax, keepdims=True)
    var = jnp.var(x32, axis=ax, keepdims=True)
    inv = lax.rsqrt(var + eps_)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = ((x32 - mean) * inv).astype(data.dtype) * jnp.reshape(gamma, shape) \
        + jnp.reshape(beta, shape)
    if parse_bool(output_mean_var):
        return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)
    return out


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    """Reference ``InstanceNorm`` (src/operator/instance_norm.cc)."""
    eps_ = parse_float(eps, 1e-3)
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps_) * jnp.reshape(gamma, shape) + \
        jnp.reshape(beta, shape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance"):
    """Reference ``L2Normalization`` (src/operator/l2_normalization.cc)."""
    eps_ = parse_float(eps, 1e-10)
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps_)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps_)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps_)
    else:
        raise ValueError(mode)
    return data / n


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Reference ``LRN`` (src/operator/nn/lrn.cc): cross-channel local
    response normalization."""
    n = parse_int(nsize, 5)
    alpha_, beta_, k_ = parse_float(alpha, 1e-4), parse_float(beta, 0.75), parse_float(knorm, 2.0)
    sq = jnp.square(data)
    half = n // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    window = (1, n) + (1,) * (data.ndim - 2)
    ssum = lax.reduce_window(padded, 0.0, lax.add,
                             window, (1,) * data.ndim, "VALID")
    return data / jnp.power(k_ + alpha_ / n * ssum, beta_)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------
@register("softmax")
def softmax(data, *length, axis=-1, temperature=None, dtype=None, use_length=False):
    """Reference ``softmax`` (src/operator/nn/softmax.cc)."""
    x = data
    if temperature is not None:
        x = x / parse_float(temperature)
    out = jax.nn.softmax(x, axis=parse_int(axis, -1))
    if dtype is not None:
        from ..base import np_dtype
        out = out.astype(np_dtype(dtype))
    return out


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None, dtype=None, use_length=False):
    x = data
    if temperature is not None:
        x = x / parse_float(temperature)
    return jax.nn.log_softmax(x, axis=parse_int(axis, -1))


@register("softmin")
def softmin(data, axis=-1, temperature=None, dtype=None, use_length=False):
    return jax.nn.softmax(-data, axis=parse_int(axis, -1))


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(jnp.reshape(data, (data.shape[0], -1)), axis=-1).reshape(data.shape)


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Reference ``SoftmaxOutput`` (src/operator/softmax_output.cc): a *loss
    layer* — forward is softmax(data); backward ignores the incoming cotangent
    and yields ``(p - onehot(label)) * grad_scale`` like the reference kernel.
    Implemented with ``jax.custom_vjp`` to preserve those semantics under
    ``jax.vjp``-driven autograd.
    """
    gs = parse_float(grad_scale, 1.0)
    ign = parse_float(ignore_label, -1.0)
    use_ign = parse_bool(use_ignore)
    norm = str(normalization)
    multi = parse_bool(multi_output)

    @jax.custom_vjp
    def _f(x, lab):
        return jax.nn.softmax(x, axis=-1 if not multi else 1)

    def _fwd(x, lab):
        out = _f(x, lab)
        return out, (out, lab)

    def _bwd(res, g):
        out, lab = res
        ax = 1 if multi else -1
        depth = out.shape[ax]
        labi = lab.astype(jnp.int32)
        oh = jax.nn.one_hot(labi, depth, dtype=out.dtype, axis=ax)
        grad = out - oh
        if use_ign:
            keep = (lab != ign)
            keep = jnp.expand_dims(keep, ax)
            grad = grad * keep.astype(out.dtype)
        scale = gs
        if norm == "batch":
            scale = scale / out.shape[0]
        elif norm == "valid" and use_ign:
            nvalid = jnp.maximum(jnp.sum((lab != ign).astype(out.dtype)), 1.0)
            grad = grad / nvalid
        grad = grad * scale
        return grad, jnp.zeros_like(lab)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


def _regression_scale(grad_scale, label):
    # reference regression_output-inl.h:200 — gradient scaled by
    # grad_scale / num_output, num_output = label.Size()/label.shape[0]
    num_output = 1
    for d in label.shape[1:]:
        num_output *= d
    return parse_float(grad_scale, 1.0) / max(num_output, 1)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    """Reference ``LinearRegressionOutput`` (src/operator/regression_output.cc):
    identity forward, (pred - label) * grad_scale/num_output backward."""
    gs = _regression_scale(grad_scale, label)

    @jax.custom_vjp
    def _f(x, lab):
        return x

    def _fwd(x, lab):
        return x, (x, lab)

    def _bwd(res, g):
        x, lab = res
        return ((x - jnp.reshape(lab, x.shape)) * gs, jnp.zeros_like(lab))

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    gs = _regression_scale(grad_scale, label)

    @jax.custom_vjp
    def _f(x, lab):
        return jax.nn.sigmoid(x)

    def _fwd(x, lab):
        return jax.nn.sigmoid(x), (x, lab)

    def _bwd(res, g):
        x, lab = res
        return ((jax.nn.sigmoid(x) - jnp.reshape(lab, x.shape)) * gs,
                jnp.zeros_like(lab))

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    gs = _regression_scale(grad_scale, label)

    @jax.custom_vjp
    def _f(x, lab):
        return x

    def _fwd(x, lab):
        return x, (x, lab)

    def _bwd(res, g):
        x, lab = res
        return (jnp.sign(x - jnp.reshape(lab, x.shape)) * gs, jnp.zeros_like(lab))

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    """Reference ``SVMOutput`` (src/operator/svm_output.cc)."""
    m = parse_float(margin, 1.0)
    reg = parse_float(regularization_coefficient, 1.0)
    linear = parse_bool(use_linear)

    @jax.custom_vjp
    def _f(x, lab):
        return x

    def _fwd(x, lab):
        return x, (x, lab)

    def _bwd(res, g):
        x, lab = res
        labi = lab.astype(jnp.int32)
        oh = jax.nn.one_hot(labi, x.shape[-1], dtype=x.dtype)
        score_correct = jnp.sum(x * oh, axis=-1, keepdims=True)
        if linear:
            viol = (x - score_correct + m) > 0
            grad = jnp.where(viol, reg * jnp.ones_like(x), jnp.zeros_like(x))
            grad = grad * (1 - oh)
            grad = grad - oh * jnp.sum(grad, axis=-1, keepdims=True)
        else:
            margin_viol = jnp.maximum(0.0, x - score_correct + m) * (1 - oh)
            grad = 2 * reg * margin_viol
            grad = grad - oh * jnp.sum(grad, axis=-1, keepdims=True)
        return grad, jnp.zeros_like(lab)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("CTCLoss", aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"))
def ctc_loss(data, label, *args, use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """Reference warp-ctc based ``CTCLoss`` (src/operator/contrib/ctc_loss.cc).
    Implemented with a JAX forward-algorithm scan (log-space)."""
    # data: (seq, batch, alphabet) as in MXNet
    seq_len, batch, nalpha = data.shape
    blank = 0 if blank_label == "first" else nalpha - 1
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    if blank_label == "first":
        pass  # labels are 1-based? MXNet: with blank first, labels are 0.. and 0 is blank-shifted
    max_lab = lab.shape[1]
    # build extended label sequence: blank, l1, blank, l2, ... blank
    ext_len = 2 * max_lab + 1
    ext = jnp.full((batch, ext_len), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    lab_valid = (lab >= 0) & (lab != blank) if blank == 0 else (lab >= 0)
    lab_lengths = jnp.sum((lab > 0 if blank == 0 else lab >= 0).astype(jnp.int32), axis=1)
    if use_label_lengths and len(args) > (1 if use_data_lengths else 0):
        lab_lengths = args[-1].astype(jnp.int32)
    data_lengths = jnp.full((batch,), seq_len, jnp.int32)
    if use_data_lengths and args:
        data_lengths = args[0].astype(jnp.int32)
    ext_lengths = 2 * lab_lengths + 1

    neg_inf = jnp.asarray(-1e30, logp.dtype)
    pos = jnp.arange(ext_len)[None, :]

    def step(alpha, t):
        lp = logp[t]  # (batch, alphabet)
        emit = jnp.take_along_axis(lp, ext, axis=1)  # (batch, ext_len)
        a_prev = alpha
        a_shift1 = jnp.concatenate([jnp.full((batch, 1), neg_inf), alpha[:, :-1]], axis=1)
        a_shift2 = jnp.concatenate([jnp.full((batch, 2), neg_inf), alpha[:, :-2]], axis=1)
        ext_shift2 = jnp.concatenate([jnp.full((batch, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
        allow_skip = (ext != blank) & (ext != ext_shift2)
        cand = jnp.logaddexp(a_prev, a_shift1)
        cand = jnp.where(allow_skip, jnp.logaddexp(cand, a_shift2), cand)
        new_alpha = cand + emit
        new_alpha = jnp.where(t < data_lengths[:, None], new_alpha, alpha)
        return new_alpha, None

    alpha0 = jnp.full((batch, ext_len), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = ext[:, 1]
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(logp[0], first_lab[:, None], axis=1)[:, 0])
    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, seq_len))
    last = ext_lengths - 1
    ll = jnp.logaddexp(
        jnp.take_along_axis(alpha, last[:, None], axis=1)[:, 0],
        jnp.take_along_axis(alpha, jnp.maximum(last - 1, 0)[:, None], axis=1)[:, 0])
    return -ll


# ---------------------------------------------------------------------------
# Dropout (stochastic — takes PRNG key as first input)
# ---------------------------------------------------------------------------
from .random_ops import STOCHASTIC_OPS as _STOCH

_STOCH.add("Dropout")


def _masked(data, mask, keep):
    return data * mask.astype(data.dtype) / keep


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dropout(key, data, keep, shape):
    """``data`` with the keep-mask ``bernoulli(key, keep, shape)`` applied.
    Under differentiation the mask is a value (``as_value``), a byte an
    element, drawn once: the forward and the backward read it, where XLA
    would run threefry again inside every matmul the masked tensor or its
    cotangent feeds."""
    _tel.count("matmul.operand", kind="recipe", op="dropout")
    return _masked(data, jax.random.bernoulli(key, keep, shape), keep)


def _dropout_fwd(key, data, keep, shape):
    mask = as_value(jax.random.bernoulli(key, keep, shape), "dropout")
    return _masked(data, mask, keep), mask


def _dropout_bwd(keep, shape, mask, g):
    return None, g / keep * mask.astype(g.dtype)


_dropout.defvjp(_dropout_fwd, _dropout_bwd)


@register("Dropout")
def dropout(key, data, p=0.5, mode="training", axes=None, cudnn_off=False,
            __training__=False):
    """Reference ``Dropout`` (src/operator/nn/dropout.cc).  ``key`` is the
    PRNG key array supplied by the frontend (JAX-native randomness)."""
    p_ = parse_float(p, 0.5)
    training = parse_bool(__training__) or mode == "always"
    if not training or p_ == 0.0:
        return data
    shape = list(data.shape)
    if axes:
        for a in parse_tuple(axes):
            shape[a] = 1
    return _dropout(key, data, 1.0 - p_, tuple(shape))


# ---------------------------------------------------------------------------
# Fused RNN op (vanilla/LSTM/GRU) — reference src/operator/rnn.cc:636
# ---------------------------------------------------------------------------
from .random_ops import _register_random


@_register_random("RNN")
def rnn(key, data, parameters, state, *rest, state_size=None, num_layers=1,
        bidirectional=False, mode="lstm", p=0.0, state_outputs=False,
        projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False,
        use_sequence_length=False, __training__=False):
    """Reference fused ``RNN`` op (src/operator/rnn.cc:636, rnn-inl.h): data
    (seq, batch, input), flat parameter vector in cuDNN canonical order,
    initial states (layers*dirs, batch, hidden).  TPU-native: a ``lax.scan``
    per layer/direction — XLA pipelines the gate matmuls onto the MXU.
    Returns output (+ final states when ``state_outputs``).
    """
    H = parse_int(state_size)
    L = parse_int(num_layers, 1)
    bidir = parse_bool(bidirectional)
    D = 2 if bidir else 1
    mode = str(mode)
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]
    state_cell = rest[0] if (mode == "lstm" and rest) else None

    seq, batch, input_size = data.shape
    offset = 0
    params = parameters

    def take_mat(n, m):
        nonlocal offset
        w = lax.dynamic_slice(params, (offset,), (n * m,)).reshape(n, m)
        offset += n * m
        return w

    def take_vec(n):
        nonlocal offset
        b = lax.dynamic_slice(params, (offset,), (n,))
        offset += n
        return b

    # cuDNN canonical layout: for each layer, for each direction:
    #   W (ngates*H, in), R (ngates*H, H); then all biases (2 vectors each).
    Ws, Rs = [], []
    for layer in range(L):
        in_sz = input_size if layer == 0 else H * D
        for d in range(D):
            Ws.append(take_mat(ngates * H, in_sz))
            Rs.append(take_mat(ngates * H, H))
    Bw, Br = [], []
    for layer in range(L):
        for d in range(D):
            Bw.append(take_vec(ngates * H))
            Br.append(take_vec(ngates * H))

    def cell_step(mode, W, R, bw, br, x_t, h, c):
        gates = x_t @ W.T + h @ R.T + bw + br
        if mode == "rnn_relu":
            h_new = jax.nn.relu(gates)
            return h_new, c
        if mode == "rnn_tanh":
            h_new = jnp.tanh(gates)
            return h_new, c
        if mode == "lstm":
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c_new = f * c + i * g
            h_new = o * jnp.tanh(c_new)
            return h_new, c_new
        if mode == "gru":
            # cuDNN GRU formulation (reset applied to (R h + br))
            xr, xz, xn = jnp.split(x_t @ W.T + bw, 3, axis=-1)
            hr, hz, hn = jnp.split(h @ R.T + br, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            return (1 - z) * n + z * h, c
        raise ValueError(mode)

    x = data
    h_finals, c_finals = [], []
    for layer in range(L):
        outs_dir = []
        for d in range(D):
            li = layer * D + d
            W, R, bw, br = Ws[li], Rs[li], Bw[li], Br[li]
            h0 = state[li]
            c0 = state_cell[li] if state_cell is not None else jnp.zeros_like(h0)
            if h0.shape[0] != batch:
                # size-1 batch placeholder (legacy begin_state) broadcasts
                h0 = jnp.broadcast_to(h0, (batch, h0.shape[-1]))
                c0 = jnp.broadcast_to(c0, (batch, c0.shape[-1]))
            xs = x if d == 0 else jnp.flip(x, 0)

            def step(carry, x_t, W=W, R=R, bw=bw, br=br):
                h, c = carry
                h2, c2 = cell_step(mode, W, R, bw, br, x_t, h, c)
                return (h2, c2), h2

            (hf, cf), ys = lax.scan(step, (h0, c0), xs)
            if d == 1:
                ys = jnp.flip(ys, 0)
            outs_dir.append(ys)
            h_finals.append(hf)
            c_finals.append(cf)
        x = outs_dir[0] if D == 1 else jnp.concatenate(outs_dir, axis=-1)
        drop = parse_float(p, 0.0)
        if parse_bool(__training__) and drop > 0 and layer < L - 1:
            # inter-layer dropout (reference rnn-inl.h applies it between
            # stacked layers, never on the final output)
            key, sub = jax.random.split(key)
            keep = 1.0 - drop
            mask = jax.random.bernoulli(sub, keep, x.shape)
            x = jnp.where(mask, x / keep, 0).astype(x.dtype)

    out = x
    if parse_bool(state_outputs):
        hN = jnp.stack(h_finals, 0)
        if mode == "lstm":
            cN = jnp.stack(c_finals, 0)
            return out, hN, cN
        return out, hN
    return out


@register("im2col")
def im2col(data, kernel=None, stride=None, dilate=None, pad=None):
    nd = _conv_dims(kernel)
    kern = parse_tuple(kernel, nd)
    stride_ = parse_tuple(stride, nd, default=(1,) * nd)
    dilate_ = parse_tuple(dilate, nd, default=(1,) * nd)
    pad_ = parse_tuple(pad, nd, default=(0,) * nd)
    n, c = data.shape[:2]
    patches = lax.conv_general_dilated_patches(
        data, kern, stride_, [(p, p) for p in pad_], rhs_dilation=dilate_)
    # patches: (N, C*prod(kern), *out_spatial)
    out_spatial = patches.shape[2:]
    flat = 1
    for s in out_spatial:
        flat *= s
    return patches.reshape(n, patches.shape[1], flat)


@register("col2im")
def col2im(data, output_size=None, kernel=None, stride=None, dilate=None,
           pad=None):
    """Reference ``col2im`` (src/operator/nn/im2col.h): scatter-add column
    patches back into an image — exactly the transpose of ``im2col``, so it
    is derived from it with ``jax.linear_transpose`` (XLA emits the native
    scatter)."""
    import jax
    out_sp = parse_tuple(output_size)
    nd_ = len(out_sp)
    kern = parse_tuple(kernel, nd_)
    n = data.shape[0]
    prod_k = 1
    for k in kern:
        prod_k *= k
    c = data.shape[1] // prod_k
    img_shape = (n, c) + tuple(out_sp)

    def fwd(img):
        return im2col(img, kernel=kernel, stride=stride, dilate=dilate,
                      pad=pad)

    transpose = jax.linear_transpose(
        fwd, jax.ShapeDtypeStruct(img_shape, data.dtype))
    return transpose(data)[0]


@register("multi_sum_sq")
def multi_sum_sq(*arrays, num_arrays=None):
    """Reference ``multi_sum_sq`` (src/operator/contrib/multi_sum_sq.cc):
    per-array sum of squares in one fused op (LARS/global-norm clipping)."""
    return jnp.stack([jnp.sum(jnp.square(a.astype(jnp.float32)))
                      for a in arrays])
