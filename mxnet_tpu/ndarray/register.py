"""Materialize the ``mx.nd`` namespace from the op table.

Reference: ``python/mxnet/ndarray/register.py:158 _make_ndarray_function`` —
MXNet builds Python functions at import time from C-side op introspection
(``MXSymbolGetAtomicSymbolInfo``).  Here the single op table
(``mxnet_tpu/ops/registry.py``) plays the role of the C registry and the
generated wrappers add the imperative conveniences: NDArray coercion,
positional-attr mapping (``nd.one_hot(x, 3)``), ``out=``, global-PRNG key
injection for stochastic ops, training-mode flag for train/predict-divergent
ops, and in-place writeback for optimizer update ops and BatchNorm aux states.
"""
from __future__ import annotations

import inspect

import numpy as _np

from .. import autograd as _ag
from .. import random as _rnd
from ..ops import registry as _reg
from ..ops.optimizer_ops import INPLACE_UPDATES
from ..ops.random_ops import STOCHASTIC_OPS
from .ndarray import NDArray, _as_nd, _wrap, invoke

# Ops whose behavior depends on autograd train/test mode (reference: ops read
# ``ctx.is_train`` from the OpContext, include/mxnet/op_attr_types.h).
MODE_DEPENDENT = {"Dropout", "BatchNorm", "RNN", "_contrib_SyncBatchNorm",
                  "_contrib_flash_attention_dropout"}

_MOMENTUM_DEFAULT = 0.9


def _batchnorm_writeback(nd_inputs, outs, attrs):
    from ..base import parse_bool, parse_float

    if _ag.is_training() and not parse_bool(attrs.get("use_global_stats", False)):
        mom = parse_float(attrs.get("momentum", _MOMENTUM_DEFAULT), _MOMENTUM_DEFAULT)
        moving_mean, moving_var = nd_inputs[3], nd_inputs[4]
        batch_mean, batch_var = outs[1], outs[2]
        moving_mean._data = mom * moving_mean._data + \
            (1 - mom) * batch_mean._data.astype(moving_mean.dtype)
        moving_var._data = mom * moving_var._data + \
            (1 - mom) * batch_var._data.astype(moving_var.dtype)


def _attr_param_names(op, stochastic):
    """Ordered names of keyword attrs, for mapping positional scalars."""
    try:
        sig = inspect.signature(op.fn)
    except (TypeError, ValueError):
        return []
    names = []
    for p in sig.parameters.values():
        if p.kind in (inspect.Parameter.VAR_POSITIONAL, inspect.Parameter.VAR_KEYWORD):
            continue
        if p.default is inspect.Parameter.empty:
            continue  # array input
        if p.name == "__training__":
            continue
        names.append(p.name)
    return names


def _input_param_names(op, stochastic):
    """Ordered names of required array inputs, so callers may pass them as
    keywords (MXNet convention: ``nd.LayerNorm(x, gamma=g, beta=b)``)."""
    try:
        sig = inspect.signature(op.fn)
    except (TypeError, ValueError):
        return []
    names = []
    for p in sig.parameters.values():
        if p.kind in (inspect.Parameter.VAR_POSITIONAL, inspect.Parameter.VAR_KEYWORD):
            continue
        if p.default is not inspect.Parameter.empty:
            continue
        names.append(p.name)
    if stochastic and names and names[0] == "key":
        names = names[1:]
    return names


_ARRAY_TYPES = (NDArray, _np.ndarray)

_SYM_CLS = None


def _sym_class():
    global _SYM_CLS
    if _SYM_CLS is None:
        from ..symbol.symbol import Symbol
        _SYM_CLS = Symbol
    return _SYM_CLS


def make_op_func(op):
    name = op.name
    stochastic = name in STOCHASTIC_OPS
    mode_dep = name in MODE_DEPENDENT
    writeback = INPLACE_UPDATES.get(name)
    is_bn = name in ("BatchNorm", "_contrib_SyncBatchNorm")
    attr_names = _attr_param_names(op, stochastic)
    input_names = _input_param_names(op, stochastic)

    def fn(*args, out=None, name=None, ctx=None, **kwargs):
        # Symbol operands delegate to the symbolic twin — lets ND-written
        # library code (gluon RNN cell steps etc.) trace symbolically
        # without an F parameter (the reference threads F=nd/sym instead).
        # Cheap on the eager hot path: one cached-class isinstance scan.
        sym_cls = _sym_class()
        if (args and any(isinstance(a, sym_cls) for a in args)) or \
                (kwargs and any(isinstance(v, sym_cls)
                                for v in kwargs.values())):
            from .. import symbol as _sym_ns
            sym_fn = getattr(_sym_ns, op.name, None)
            if sym_fn is None:
                raise TypeError(f"op {op.name} has no symbolic form")
            if out is not None:
                raise TypeError(
                    f"op {op.name}: out= is not supported with Symbol "
                    f"operands (a graph node has no output buffer)")
            mixed = [a for a in list(args) + list(kwargs.values())
                     if isinstance(a, _ARRAY_TYPES)]
            if mixed:
                raise TypeError(
                    f"op {op.name}: cannot mix Symbol and NDArray "
                    f"operands — wrap constants as mx.sym.Variable-fed "
                    f"inputs or run the op imperatively")
            if name is not None:
                kwargs["name"] = name
            return sym_fn(*args, **kwargs)
        # split positional args into array inputs and positional attrs
        i = 0
        nd_inputs = []
        while i < len(args):
            a = args[i]
            if isinstance(a, _ARRAY_TYPES) or (hasattr(a, "shape") and hasattr(a, "dtype")):
                nd_inputs.append(a if isinstance(a, NDArray) else _as_nd(a))
                i += 1
            else:
                break
        # named array inputs passed as keywords fill remaining input slots
        if len(nd_inputs) < len(input_names):
            for pname in input_names[len(nd_inputs):]:
                if pname in kwargs and (isinstance(kwargs[pname], _ARRAY_TYPES)
                                        or hasattr(kwargs[pname], "shape")):
                    nd_inputs.append(_as_nd(kwargs.pop(pname)))
                else:
                    break
        attrs = dict(kwargs)
        for v, pname in zip(args[i:], attr_names):
            attrs.setdefault(pname, v)
        if mode_dep:
            attrs["__training__"] = _ag.is_training()
        raw_in = list(nd_inputs)
        if stochastic:
            raw_in = [_wrap(_rnd.next_key())] + raw_in
        # writeback ops (optimizer in-place updates, BatchNorm aux-state
        # moving averages) rebind input buffers from the op's outputs right
        # here — they need concrete results NOW, so the lazy-bulking
        # recorder must not capture them (engine/recorder.py fallback
        # matrix)
        result = invoke(op, raw_in, attrs,
                        out=None if (writeback or is_bn) else out,
                        bulk=not (writeback or is_bn))
        if is_bn:
            from ..base import parse_bool
            outs = result if isinstance(result, list) else [result]
            _batchnorm_writeback(nd_inputs, outs, attrs)
            if parse_bool(attrs.get("output_mean_var", False)):
                result = outs  # (out, batch_mean, batch_var) like the reference
            else:
                result = outs[0]
                if out is not None:
                    out._data, out._ag_node = result._data, result._ag_node
                    result = out
        elif writeback:
            outs = result if isinstance(result, list) else [result]
            if isinstance(writeback, tuple) and writeback[0] == "strided":
                # multi-tensor updates: per-group (in_off, out_off) pairs
                # repeated every (in_stride, out_stride) tensors
                _, in_stride, out_stride, pairs = writeback
                ngroups = len(outs) // out_stride
                updated = []
                for g in range(ngroups):
                    for io, oo in pairs:
                        nd_inputs[g * in_stride + io]._data = \
                            outs[g * out_stride + oo]._data
                    updated.append(nd_inputs[g * in_stride + pairs[0][0]])
                result = updated if len(updated) > 1 else updated[0]
            else:
                for in_idx, out_idx in writeback:
                    nd_inputs[in_idx]._data = outs[out_idx]._data
                result = nd_inputs[writeback[0][0]]
            if out is not None:
                if isinstance(result, list):
                    for o, r in zip(out, result):
                        o._data = r._data
                    result = out
                else:
                    out._data = result._data
                    result = out
        if ctx is not None and isinstance(result, NDArray) and not nd_inputs:
            result = result.as_in_context(ctx)
        return result

    fn.__name__ = name
    fn.__doc__ = op.doc or f"Operator {name} (see mxnet_tpu/ops)."
    return fn


def populate(module):
    """Install generated op functions into ``module`` (the analog of
    ``_init_op_module``, reference ``python/mxnet/base.py:579``)."""
    installed = {}
    for opname in _reg.all_names():
        op = _reg.get(opname)
        f = make_op_func(op)
        f.__name__ = opname
        setattr(module, opname, f)
        installed[opname] = f
    return installed
