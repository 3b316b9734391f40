"""Aggregated (multi-tensor) optimizer updates — one jit call per group.

Reference being rebuilt: the ``multi_sgd_update`` / ``multi_sgd_mom_update`` /
``multi_mp_sgd*`` kernel family (``src/operator/optimizer_op.cc:345-476``) and
the ``MXNET_OPTIMIZER_AGGREGATION_SIZE`` knob (``optimizer.py:511`` SGD): on
models with hundreds of small tensors the per-parameter update launch
dominates step time, so MXNet 1.5 batches up to N parameters into one fused
kernel launch.

TPU-native redesign: instead of hand-written variadic kernels, parameters are
grouped by (optimizer class, weight dtype, static hyperparameter signature,
multi-precision, sparsity) and each group's whole ``(weights, grads, states)``
pytree is updated by ONE jitted function with ``donate_argnums`` on weights
and optimizer state — the in-place HBM semantics of the reference engine's
write-dependency model.  Scalar hyperparameters that change across steps
(lr schedules, rescale_grad, per-parameter lr/wd multipliers, Adam's
bias-corrected lr) are *traced* arguments, so steady-state steps replay the
same executable: after step 1 the group-signature cache takes zero compile
misses (observable via the ``optimizer.compile_miss`` telemetry event).

Fallbacks (per-parameter ``update_multi_precision``) are taken for:
row-sparse / compressed gradients (the lazy_update O(nnz) kernels stay
per-parameter), bare-fp16 weights without multi_precision, optimizer classes
without a registered rule (or subclasses of one — they may override
``update``), and ``MXNET_OPTIMIZER_AGGREGATION_SIZE <= 1``.

Telemetry (when the bus is enabled): ``optimizer.update_group`` sub-spans
inside ``trainer.update``, ``optimizer.update_groups`` / count the group
dispatches, ``optimizer.state_bytes`` gauges the tracked slot memory, and
``optimizer.update_calls`` counts dispatches (group calls + per-param
fallbacks) so dispatches/step is a measurable number
(``tests/test_optimizer_aggregate.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy

from ..analysis import sanitizer as _san
from ..ndarray import NDArray
from ..resilience import faults as _faults
from ..telemetry import bus as _tel

__all__ = ["update_multi", "functional_update", "registered_rules",
           "cache_info", "clear_cache"]


def _is_dense(arr):
    """True for a plain dense NDArray (no row-sparse backing)."""
    return isinstance(arr, NDArray) and getattr(arr, "_rs", None) is None


def _state_leaves(state):
    """Flatten an optimizer state pytree to its NDArray leaves (None leaves
    are structural absence — e.g. momentum==0 — and are dropped; the static
    group signature fixes the arity).  Returns None if a leaf is neither
    None nor a dense NDArray (custom state objects → fallback)."""
    if state is None:
        return ()
    if isinstance(state, NDArray):
        return (state,) if _is_dense(state) else None
    if isinstance(state, (tuple, list)):
        out = []
        for s in state:
            leaves = _state_leaves(s)
            if leaves is None:
                return None
            out.extend(leaves)
        return tuple(out)
    return None


def _clip(g, hyper, has_clip):
    if has_clip:
        return jnp.clip(g, -hyper["clip_gradient"], hyper["clip_gradient"])
    return g


# --------------------------------------------------------------------- rules
def _has_clip(opt):
    """Clipping is armed only for a POSITIVE clip_gradient — the exact gate
    of the per-param ops (``_apply_wd`` requires ``> 0``; the optimizer
    kwargs use truthiness), so 0.0 / negative values stay no-ops."""
    return opt.clip_gradient is not None and opt.clip_gradient > 0


class _Rule:
    """One aggregation recipe per optimizer class.

    ``signature``/``hyper`` split the optimizer's knobs into the static part
    (changes recompile: momentum on/off, clipping on/off, centered, ...) and
    the traced scalar part (changes are free: lr, wd, rescale_grad, betas).
    ``step`` is the pure per-tensor update — its math must match the eager
    per-parameter op bit-for-bit in structure so aggregated == per-param
    within float tolerance (asserted by tests/test_optimizer_aggregate.py).
    """

    #: True when ``extras`` replays a host-side recurrence whose snapshots
    #: depend on the ORDER members are processed in (Nadam's m_schedule).
    #: Such a rule only aggregates when every member lands in one group
    #: with no fallbacks — any split would permute the per-param order.
    order_sensitive = False

    def signature(self, opt):
        return (_has_clip(opt),)

    def hyper(self, opt):
        return {"rescale_grad": float(opt.rescale_grad),
                "clip_gradient": float(opt.clip_gradient or 0.0)}

    def state_arity(self, sig):
        raise NotImplementedError

    def lrs(self, opt, indices):
        """Per-tensor learning rates (already bias-corrected where the
        per-param path folds the correction into lr, e.g. Adam)."""
        return opt._get_lrs(indices)

    def extras(self, opt, indices):
        """Optional per-member traced scalars beyond lr/wd (a tuple of
        floats per member, fixed arity per rule) — how Nadam's
        host-side momentum schedule rides into the jitted group without
        recompiling.  This hook may mutate optimizer bookkeeping exactly
        like the per-param ``update`` would (member order = list order).
        None means the rule needs no extras."""
        return None

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        raise NotImplementedError


class _SGDRule(_Rule):
    def signature(self, opt):
        return (opt.momentum != 0.0, _has_clip(opt))

    def hyper(self, opt):
        h = super().hyper(opt)
        h["momentum"] = float(opt.momentum)
        return h

    def state_arity(self, sig):
        has_mom, _ = sig
        return 1 if has_mom else 0

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        has_mom, has_clip = sig
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip) + wd * w
        if has_mom:
            (mom,) = state
            new_mom = hyper["momentum"] * mom - lr * g
            return w + new_mom, (new_mom,)
        return w - lr * g, ()


class _NAGRule(_Rule):
    def signature(self, opt):
        return (opt.momentum != 0.0, _has_clip(opt))

    def hyper(self, opt):
        h = super().hyper(opt)
        h["momentum"] = float(opt.momentum)
        return h

    def state_arity(self, sig):
        has_mom, _ = sig
        return 1 if has_mom else 0

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        has_mom, has_clip = sig
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip) + wd * w
        if has_mom:
            (mom,) = state
            mu = hyper["momentum"]
            new_mom = mu * mom + g
            return w - lr * (g + mu * new_mom), (new_mom,)
        return w - lr * g, ()


class _SignumRule(_Rule):
    def signature(self, opt):
        return (opt.momentum != 0.0, _has_clip(opt))

    def hyper(self, opt):
        h = super().hyper(opt)
        h["momentum"] = float(opt.momentum)
        h["wd_lh"] = float(opt.wd_lh)
        return h

    def state_arity(self, sig):
        has_mom, _ = sig
        return 1 if has_mom else 0

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        has_mom, has_clip = sig
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip)
        if has_mom:
            (mom,) = state
            mu = hyper["momentum"]
            new_mom = mu * mom - (1 - mu) * g
            return w + lr * (jnp.sign(new_mom) - hyper["wd_lh"] * w), \
                (new_mom,)
        return w - lr * (jnp.sign(g) + wd * w), ()


class _AdamRule(_Rule):
    def hyper(self, opt):
        h = super().hyper(opt)
        h.update(beta1=float(opt.beta1), beta2=float(opt.beta2),
                 epsilon=float(opt.epsilon))
        return h

    def state_arity(self, sig):
        return 2

    def lrs(self, opt, indices):
        # per-param path folds the bias correction into lr with the
        # per-index step count t (optimizer.py Adam.update)
        out = []
        for lr, i in zip(opt._get_lrs(indices), indices):
            t = opt._index_update_count[i]
            out.append(lr * (1. - opt.beta2 ** t) ** 0.5
                       / (1. - opt.beta1 ** t))
        return out

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        (has_clip,) = sig
        mean, var = state
        b1, b2 = hyper["beta1"], hyper["beta2"]
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip) + wd * w
        new_mean = b1 * mean + (1 - b1) * g
        new_var = b2 * var + (1 - b2) * jnp.square(g)
        new_w = w - lr * new_mean / (jnp.sqrt(new_var) + hyper["epsilon"])
        return new_w, (new_mean, new_var)


class _RMSPropRule(_Rule):
    def signature(self, opt):
        return (bool(opt.centered), _has_clip(opt),
                opt.clip_weights is not None and opt.clip_weights > 0)

    def hyper(self, opt):
        h = super().hyper(opt)
        h.update(gamma1=float(opt.gamma1), gamma2=float(opt.gamma2),
                 epsilon=float(opt.epsilon),
                 clip_weights=float(opt.clip_weights or 0.0))
        return h

    def state_arity(self, sig):
        centered, _, _ = sig
        return 3 if centered else 1

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        centered, has_clip, has_cw = sig
        gr = _clip(g * hyper["rescale_grad"], hyper, has_clip) + wd * w
        g1 = hyper["gamma1"]
        if centered:
            n, gbar, delta = state
            new_n = (1 - g1) * jnp.square(gr) + g1 * n
            new_g = (1 - g1) * gr + g1 * gbar
            new_delta = hyper["gamma2"] * delta - lr * gr / jnp.sqrt(
                new_n - jnp.square(new_g) + hyper["epsilon"])
            new_w = w + new_delta
            if has_cw:
                new_w = jnp.clip(new_w, -hyper["clip_weights"],
                                 hyper["clip_weights"])
            return new_w, (new_n, new_g, new_delta)
        (n,) = state
        new_n = (1 - g1) * jnp.square(gr) + g1 * n
        new_w = w - lr * gr / jnp.sqrt(new_n + hyper["epsilon"])
        if has_cw:
            new_w = jnp.clip(new_w, -hyper["clip_weights"],
                             hyper["clip_weights"])
        return new_w, (new_n,)


class _AdamaxRule(_Rule):
    def hyper(self, opt):
        h = super().hyper(opt)
        h.update(beta1=float(opt.beta1), beta2=float(opt.beta2))
        return h

    def state_arity(self, sig):
        return 2

    def lrs(self, opt, indices):
        # per-param path folds the infinity-norm bias correction into lr
        # with the per-index step count t (optimizer.py Adamax.update)
        out = []
        for lr, i in zip(opt._get_lrs(indices), indices):
            t = opt._index_update_count[i]
            out.append(lr / (1. - opt.beta1 ** t))
        return out

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        (has_clip,) = sig
        m, u = state
        b1 = hyper["beta1"]
        # per-param order (_begin_update): rescale, clip, THEN wd
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip) + wd * w
        new_m = b1 * m + (1. - b1) * g
        new_u = jnp.maximum(hyper["beta2"] * u, jnp.abs(g))
        return w - lr * new_m / new_u, (new_m, new_u)


class _NadamRule(_Rule):
    order_sensitive = True

    def hyper(self, opt):
        h = super().hyper(opt)
        h.update(beta1=float(opt.beta1), beta2=float(opt.beta2),
                 epsilon=float(opt.epsilon))
        return h

    def state_arity(self, sig):
        return 2

    def extras(self, opt, indices):
        """Per-member momentum-schedule scalars.  The per-param path
        multiplies ``opt.m_schedule`` once per parameter per update —
        replicate that recurrence (including the mutation) host-side, in
        member order, and hand each member its own snapshot as traced
        arguments so the schedule never recompiles the group."""
        out = []
        b1, sd = opt.beta1, opt.schedule_decay
        for i in indices:
            t = opt._index_update_count[i]
            momentum_t = b1 * (1. - 0.5 * (0.96 ** (t * sd)))
            momentum_t_1 = b1 * (1. - 0.5 * (0.96 ** ((t + 1) * sd)))
            opt.m_schedule = opt.m_schedule * momentum_t
            out.append((momentum_t, momentum_t_1, opt.m_schedule,
                        opt.m_schedule * momentum_t_1,
                        1. - opt.beta2 ** t))
        return out

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        (has_clip,) = sig
        m, v = state
        mom_t, mom_t_1, m_sched, m_sched_next, v_corr = extra
        b1, b2 = hyper["beta1"], hyper["beta2"]
        # per-param order (Nadam.update): rescale + wd, THEN clip
        g = _clip(g * hyper["rescale_grad"] + wd * w, hyper, has_clip)
        new_m = b1 * m + (1. - b1) * g
        new_v = b2 * v + (1. - b2) * g * g
        g_prime = g / (1. - m_sched)
        m_prime = new_m / (1. - m_sched_next)
        v_prime = new_v / v_corr
        m_bar = (1. - mom_t) * g_prime + mom_t_1 * m_prime
        return w - lr * m_bar / (jnp.sqrt(v_prime) + hyper["epsilon"]), \
            (new_m, new_v)


class _FTMLRule(_Rule):
    def hyper(self, opt):
        h = super().hyper(opt)
        h.update(beta1=float(opt.beta1), beta2=float(opt.beta2),
                 epsilon=float(opt.epsilon))
        return h

    def state_arity(self, sig):
        return 3                      # (d, v, z)

    def extras(self, opt, indices):
        """Per-member bias-correction scalars: the per-param op bakes the
        step count ``t`` into its attrs (one recompile per step!); here
        ``((1 - b1**t)/lr, 1 - b2**t)`` ride as traced arguments instead,
        so advancing t never recompiles the group.  The divisions happen
        host-side in float64 — exactly where the per-param op computes its
        python-float constants — so the f32 roundings match."""
        out = []
        b1, b2 = opt.beta1, opt.beta2
        lrs = opt._get_lrs(indices)
        for lr, i in zip(lrs, indices):
            t = opt._index_update_count[i]
            out.append(((1. - b1 ** t) / lr, 1. - b2 ** t))
        return out

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        (has_clip,) = sig
        d, v, z = state
        b1, b2 = hyper["beta1"], hyper["beta2"]
        b1_corr_over_lr, b2_corr = extra
        # per-param order (_apply_wd in ops/optimizer_ops.py ftml_update):
        # rescale, clip, THEN + wd*w
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip) + wd * w
        new_v = b2 * v + (1. - b2) * jnp.square(g)
        d_t = b1_corr_over_lr * (jnp.sqrt(new_v / b2_corr)
                                 + hyper["epsilon"])
        sigma_t = d_t - b1 * d
        new_z = b1 * z + (1. - b1) * g - sigma_t * w
        return -new_z / d_t, (d_t, new_v, new_z)


class _FtrlRule(_Rule):
    def hyper(self, opt):
        h = super().hyper(opt)
        h.update(lamda1=float(opt.lamda1), beta=float(opt.beta))
        return h

    def state_arity(self, sig):
        return 2                      # (z, n)

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        (has_clip,) = sig
        z, n = state
        # per-param order (ftrl_update): rescale, clip — NO wd on the grad
        # (wd enters the proximal denominator below)
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip)
        new_n = n + jnp.square(g)
        sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
        new_z = z + g - sigma * w
        l1 = hyper["lamda1"]
        new_w = jnp.where(
            jnp.abs(new_z) > l1,
            -(new_z - jnp.sign(new_z) * l1)
            / ((hyper["beta"] + jnp.sqrt(new_n)) / lr + wd),
            jnp.zeros_like(w))
        return new_w, (new_z, new_n)


class _AdaGradRule(_Rule):
    def hyper(self, opt):
        h = super().hyper(opt)
        h["epsilon"] = float(opt.float_stable_eps)
        return h

    def state_arity(self, sig):
        return 1

    def step(self, w, g, state, lr, wd, hyper, sig, extra=()):
        (has_clip,) = sig
        (history,) = state
        g = _clip(g * hyper["rescale_grad"], hyper, has_clip)
        new_hist = history + jnp.square(g)
        div = g / jnp.sqrt(new_hist + hyper["epsilon"])
        return w + (div + w * wd) * -lr, (new_hist,)


def _rules():
    """Exact-class rule table, built lazily to dodge the import cycle with
    optimizer.py.  Exact ``type()`` match only: a subclass may override
    ``update`` and must keep the per-parameter path."""
    from .optimizer import (FTML, SGD, NAG, Adam, AdaGrad, Adamax, Ftrl,
                            Nadam, RMSProp, Signum)
    return {SGD: ("sgd", _SGDRule()),
            NAG: ("nag", _NAGRule()),
            Signum: ("signum", _SignumRule()),
            Adam: ("adam", _AdamRule()),
            RMSProp: ("rmsprop", _RMSPropRule()),
            AdaGrad: ("adagrad", _AdaGradRule()),
            Adamax: ("adamax", _AdamaxRule()),
            Nadam: ("nadam", _NadamRule()),
            FTML: ("ftml", _FTMLRule()),
            Ftrl: ("ftrl", _FtrlRule())}


_RULES = None


def registered_rules():
    global _RULES
    if _RULES is None:
        _RULES = _rules()
    return _RULES


# ------------------------------------------------------------ compiled cache
# (rule_name, static_sig, mp, members_sig) -> jitted group update.  Each
# entry compiles exactly once, so a cache miss IS a compile (the telemetry
# event the "zero recompiles after step 1" acceptance check reads).
_compiled = {}


def cache_info():
    """(n_entries, keys) of the compiled-group cache — test/debug surface."""
    return len(_compiled), list(_compiled)


def clear_cache():
    _compiled.clear()


def _build_group_fn(rule, sig, mp):
    """One jitted update over the whole group pytree.  Weights (arg 0) and
    state (arg 2) are donated: their HBM buffers are reused for the outputs,
    matching the reference engine's in-place write-dependency model.  Grads
    are NOT donated (callers may inspect or re-reduce them)."""

    def group_update(weights, grads, states, lrs, wds, extras, hyper):
        new_ws, new_ss = [], []
        for w, g, s, lr, wd, ex in zip(weights, grads, states, lrs, wds,
                                       extras):
            if mp:
                master, inner = s[0], tuple(s[1:])
                new_master, new_inner = rule.step(
                    master, g.astype(jnp.float32), inner, lr, wd, hyper,
                    sig, ex)
                new_ws.append(new_master.astype(w.dtype))
                new_ss.append([new_master] + list(new_inner))
            else:
                new_w, new_s = rule.step(w, g, tuple(s), lr, wd, hyper,
                                         sig, ex)
                new_ws.append(new_w)
                new_ss.append(list(new_s))
        return new_ws, new_ss

    return jax.jit(group_update, donate_argnums=(0, 2))


def _members_sig(weights, grads, state_leaves):
    sig = []
    for w, g, leaves in zip(weights, grads, state_leaves):
        sig.append((tuple(w.shape), str(w.dtype), str(g.dtype),
                    tuple((tuple(s.shape), str(s.dtype)) for s in leaves)))
    return tuple(sig)


def _group_key_for(opt, rule_entry, weight, grad, state):
    """Group key + flattened state for one member, or None → fallback."""
    name, rule = rule_entry
    if not (_is_dense(weight) and _is_dense(grad)):
        return None
    # one jit call commits to one device: parameters living on different
    # devices land in different groups, and a member whose grad sits on
    # another device than its weight falls back to the per-param path
    devices = frozenset(weight._data.devices())
    if frozenset(grad._data.devices()) != devices:
        return None
    sig = rule.signature(opt)
    mp = False
    leaves = None
    if weight.dtype == numpy.float16:
        # aggregate fp16 only through the fp32-master multi-precision path
        # (bare-fp16 accumulation keeps the per-param warning behavior)
        if not (opt.multi_precision and isinstance(state, (tuple, list))
                and len(state) == 2 and _is_dense(state[0])
                and state[0].dtype == numpy.float32):
            return None
        inner = _state_leaves(state[1])
        if inner is None or len(inner) != rule.state_arity(sig):
            return None
        mp = True
        leaves = (state[0],) + inner
    else:
        leaves = _state_leaves(state)
        if leaves is None or len(leaves) != rule.state_arity(sig):
            return None
        if grad.dtype != weight.dtype:
            return None
    for leaf in leaves:
        if frozenset(leaf._data.devices()) != devices:
            return None
    return (name, rule, sig, mp, str(weight.dtype), devices), leaves


def update_multi(opt, indices, weights, grads, states):
    """Apply ``opt`` to parallel lists of (index, weight, grad, state),
    aggregating compatible members into one jitted call per group and
    falling back to ``update_multi_precision`` for the rest.

    Weight and state NDArrays are mutated in place (handle rebinding), so
    state identity — and ``Updater.get_states`` serialization — is
    byte-compatible with the per-parameter path.
    """
    agg_size = getattr(opt, "aggregate_num", 0)
    rule_entry = registered_rules().get(type(opt)) \
        if agg_size and agg_size > 1 else None

    groups = {}     # key -> list of (position, state_leaves)
    fallback = []
    if rule_entry is not None:
        donated = set()   # backing-buffer ids already claimed for donation
        for pos, (w, g, s) in enumerate(zip(weights, grads, states)):
            keyed = _group_key_for(opt, rule_entry, w, g, s)
            if keyed is None:
                fallback.append(pos)
                continue
            key, leaves = keyed
            # a buffer may be donated at most once per step: tied handles
            # (shared weights, aliased state) take the per-param path
            bufs = {id(w._data)} | {id(leaf._data) for leaf in leaves}
            if len(bufs) < 1 + len(leaves) or bufs & donated:
                fallback.append(pos)
                continue
            donated |= bufs
            groups.setdefault(key, []).append((pos, leaves))
    else:
        fallback = list(range(len(weights)))

    if (groups and rule_entry[1].order_sensitive
            and (fallback or len(groups) > 1)):
        # Nadam's m_schedule snapshots depend on processing ORDER: the
        # per-param reference walks members in caller index order, which
        # multiple groups (e.g. mixed fp32 + fp16-mp params) or
        # interleaved fallbacks would permute.  A single group keeps
        # ascending position order across its chunks; anything else must
        # take the per-param path wholesale to replicate exactly.
        fallback = list(range(len(weights)))
        groups = {}

    if _faults.active:
        # resilience drill site: fails BEFORE any group mutates, so an
        # injected fault never leaves a half-applied step behind
        _faults.check("optimizer.apply")

    tel_on = _tel.enabled
    n_dispatch = 0
    for key, members in groups.items():
        name, rule, sig, mp, _dtype, _devices = key
        for lo in range(0, len(members), agg_size):
            chunk = members[lo:lo + agg_size]
            n_dispatch += 1
            _run_group(opt, name, rule, sig, mp, chunk, indices, weights,
                       grads, tel_on)

    for pos in fallback:
        n_dispatch += 1
        opt.update_multi_precision(indices[pos], weights[pos], grads[pos],
                                   states[pos])

    if tel_on:
        _tel.count("optimizer.update_calls", n_dispatch)
        _tel.count("optimizer.aggregated_params",
                   len(weights) - len(fallback))
        if fallback:
            _tel.count("optimizer.fallback_params", len(fallback))
        _tel.gauge("optimizer.update_groups", len(groups))
        _tel.gauge("optimizer.state_bytes", _state_bytes(states))


def _state_bytes(states):
    total = 0
    for s in states:
        leaves = _state_leaves(s) if not isinstance(s, NDArray) \
            else (s,)
        if leaves:
            for leaf in leaves:
                n = 1
                for d in leaf.shape:
                    n *= int(d)
                total += n * leaf.dtype.itemsize
    return total


def functional_update(fopt, params, grads, state, lr):
    """ONE jitted dispatch for a whole :class:`FunctionalOptimizer` step.

    The SPMD follow-up to the eager path above (ROADMAP): an eager caller
    driving ``parallel.FunctionalOptimizer.update`` directly — outside
    ``make_train_step``'s jit — would pay one dispatch per parameter per
    slot.  Here the whole ``(params, grads, state)`` dict updates in one
    jitted call compiled once per (optimizer signature, members signature)
    through the SAME compiled-group cache as ``update_multi``, with the same
    ``optimizer.compile_miss`` telemetry: steady-state steps take zero
    compile misses and ``lr`` (schedules, Adam bias correction) is traced,
    so changing it never recompiles.

    Purely functional — nothing is donated or mutated: callers keep their
    input arrays (``update`` returns fresh ``(params', state')``).  The
    per-tensor math is ``fopt.update_one`` itself (the ``optimizer_ops``
    kernels), so numerics are identical to the inline path bit for bit.
    """
    names = tuple(sorted(params))
    # every non-lr hyperparameter is baked into the trace (update_one reads
    # them off fopt), so they key the cache; lr is the traced argument —
    # schedules and bias correction never recompile
    static = (fopt.name, float(fopt.momentum), float(fopt.wd),
              float(fopt.beta1), float(fopt.beta2), float(fopt.epsilon),
              float(fopt.gamma1), float(fopt.rescale_grad),
              float(fopt.clip_gradient))
    members = tuple(
        (k, tuple(params[k].shape), str(params[k].dtype),
         str(grads[k].dtype),
         tuple((tuple(s.shape), str(s.dtype)) for s in state[k]))
        for k in names)
    cache_key = ("functional", static, False, members)
    fn = _compiled.get(cache_key)
    tel_on = _tel.enabled
    if fn is None:
        # close over a FROZEN copy, not the live fopt: the cache key holds
        # these hyperparam VALUES, but jax may retrace the closure long
        # after this miss (e.g. lr arriving as a new aval) — a caller who
        # mutated fopt in the meantime would otherwise bake stale values
        # into an entry keyed by the old ones
        import copy
        snap = copy.copy(fopt)
        (snap.momentum, snap.wd, snap.beta1, snap.beta2, snap.epsilon,
         snap.gamma1, snap.rescale_grad, snap.clip_gradient) = static[1:]

        def group_update(params, grads, state, lr):
            new_params, new_state = {}, {}
            for k in names:
                w, s = snap.update_one(params[k], grads[k], state[k], lr)
                new_params[k] = w
                new_state[k] = s
            return new_params, new_state

        fn = jax.jit(group_update)
        _compiled[cache_key] = fn
        if tel_on:
            _tel.count("optimizer.compile_misses")
            _tel.instant("optimizer.compile_miss", opt=fopt.name,
                         n=len(names), signature="functional",
                         shapes=repr([m[1] for m in members]))
    if tel_on:
        _tel.count("optimizer.update_calls")
        _tel.count("optimizer.aggregated_params", len(names))
        _tel.gauge("optimizer.update_groups", 1)
    if _faults.active:
        _faults.check("optimizer.apply")
    with _tel.span("optimizer.update_group", opt=fopt.name, n=len(names),
                   mp=False):
        return fn(params, grads, state, lr)


def _run_group(opt, name, rule, sig, mp, chunk, indices, weights, grads,
               tel_on):
    """Dispatch one compiled group update and rebind the outputs."""
    positions = [pos for pos, _ in chunk]
    idxs = [indices[pos] for pos in positions]
    ws = [weights[pos] for pos in positions]
    gs = [grads[pos] for pos in positions]
    leaf_lists = [list(leaves) for _, leaves in chunk]

    # reference aggregated path: bump every member's update count first,
    # then resolve the scheduled lr/wd for the whole chunk
    opt._update_count(idxs)
    lrs = [float(lr) for lr in rule.lrs(opt, idxs)]
    wds = [float(wd) for wd in opt._get_wds(idxs)]
    extras = rule.extras(opt, idxs)
    if extras is None:
        extras = [()] * len(idxs)
    hyper = rule.hyper(opt)

    w_data = [w._data for w in ws]
    g_data = [g._data for g in gs]
    s_data = [[leaf._data for leaf in leaves] for leaves in leaf_lists]

    cache_key = (name, sig, mp, _members_sig(ws, gs, leaf_lists))
    fn = _compiled.get(cache_key)
    if fn is None:
        fn = _build_group_fn(rule, sig, mp)
        _compiled[cache_key] = fn
        if tel_on:
            _tel.count("optimizer.compile_misses")
            _tel.instant("optimizer.compile_miss", opt=name, n=len(ws),
                         signature=repr((sig, mp)),
                         shapes=repr([m[0] for m in cache_key[3]]))

    with _tel.span("optimizer.update_group", opt=name, n=len(ws), mp=mp):
        new_w, new_s = fn(w_data, g_data, s_data, lrs, wds, extras, hyper)

    if _san.donation:
        # the group call donated weights (arg 0) and state (arg 2): poison
        # the pre-call buffers so any alias that dodged the rebind below
        # raises with this site named instead of reading reused memory
        site = (f"optimizer.aggregate group {name!r} "
                f"(update_multi, {len(ws)} params, donated weights+state)")
        _san.poison(w_data, site)
        _san.poison([leaf for leaves in s_data for leaf in leaves], site)

    # rebind in place: same NDArray handles, fresh (donated) buffers —
    # the frontend analog of the engine writing through WriteTo vars
    for w, nw in zip(ws, new_w):
        w._data = nw
    for leaves, ns in zip(leaf_lists, new_s):
        for leaf, nleaf in zip(leaves, ns):
            leaf._data = nleaf
