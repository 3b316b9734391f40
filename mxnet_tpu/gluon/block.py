"""Gluon Block / HybridBlock / SymbolBlock.

Reference being rebuilt: ``python/mxnet/gluon/block.py`` — ``Block`` (eager
container with name scoping and parameter management, ``block.py:128``),
``HybridBlock`` (``block.py:679``; ``hybridize()`` → ``_build_cache:756`` →
C++ ``CachedOp`` graph capture, ``src/imperative/cached_op.cc:904``), and
``SymbolBlock`` (``block.py:960``).

TPU-native redesign of CachedOp: instead of capturing an NNVM graph and
replaying it through the dependency engine, ``hybridize()`` wraps the block's
forward in ``jax.jit``: parameters and inputs become traced arguments, PRNG
keys thread through ``random.key_scope`` as a dynamic argument, and mutated
auxiliary states (BatchNorm moving stats) are returned as extra outputs and
written back — the functional analog of the reference's in-place aux updates.
``static_alloc``/``static_shape`` are accepted for API compatibility; XLA's
buffer assignment subsumes the reference's memory planning
(``src/nnvm/plan_memory.cc``).  The jitted callable is recorded on the
autograd tape as ONE composite op — exactly how the reference registers
``_CachedOp`` as an operator so it can be recorded and nested.  The block
that is CALLED owns the program: the hybridized blocks below it run op by
op inside its trace (as the reference's children run symbolically inside
the parent's graph) and build a cached op only when called on their own.
"""
from __future__ import annotations

import re
import threading
import warnings
from collections import OrderedDict

from .. import autograd, ndarray
from .. import random as _rnd
from ..context import current_context
from ..ndarray import NDArray
from ..telemetry import bus as _tel
from .parameter import DeferredInitializationError, Parameter, ParameterDict
from .utils import _indent


class _BlockScope:
    """Name manager for Blocks (reference ``block.py:34``)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix and params for a new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager.current.get(None, hint) + "_"
            params = ParameterDict(prefix) if params is None \
                else ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        parent = current._block.params
        params = ParameterDict(parent.prefix + prefix, parent._shared) \
            if params is None else ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if not self._block._empty_prefix:
            from ..name import Prefix
            self._old_scope = getattr(_BlockScope._current, "value", None)
            _BlockScope._current.value = self
            self._name_scope = Prefix(self._block.prefix)
            self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if not self._block._empty_prefix:
            scope, self._name_scope = self._name_scope, None
            scope.__exit__(ptype, value, trace)
            _BlockScope._current.value = self._old_scope


def _flatten(args, inout_str):
    if args is None:
        # None is static structure (optional block arguments) — carried in
        # the format so jitted replay reconstructs the call signature
        return [], -1
    if isinstance(args, NDArray):
        return [args], int(0)
    from ..symbol import Symbol
    if isinstance(args, Symbol):
        n_out = len(args.list_outputs())
        return [args], (n_out if n_out > 1 else 0)
    assert isinstance(args, (list, tuple)), \
        f"HybridBlock {inout_str} must be (nested) list of Symbol or NDArray, " \
        f"but got {args} of type {type(args)}"
    parts = [_flatten(i, inout_str) for i in args]
    return [leaf for flat, _ in parts for leaf in flat], \
        [fmt for _, fmt in parts]


def io_signature(arrays):
    """Shape/dtype signature key for a flat list of arrays.

    The ONE format shared by ``CachedOp``'s recompile tracking,
    :meth:`HybridBlock.compile_for` / :meth:`HybridBlock.compiled_signatures`,
    and ``serving.ModelRuntime``'s compile-miss check — all three must agree
    byte-for-byte or warmed shapes stop matching."""
    return (tuple(tuple(x.shape) for x in arrays),
            tuple(str(x.dtype) for x in arrays))


def _regroup(args, fmt):
    if isinstance(fmt, int):
        if fmt == -1:
            return None, args
        if fmt == 0:
            return args[0], args[1:]
        return args[:fmt], args[fmt:]
    assert isinstance(args, (list, tuple)), \
        f"HybridBlock output must be (nested) list of Symbol or NDArray, " \
        f"but got {args} of type {type(args)}"
    grouped = []
    for sub_fmt in fmt:
        piece, args = _regroup(args, sub_fmt)
        grouped.append(piece)
    return grouped, args


# bumped on EVERY child registration anywhere — lets hybridized blocks
# skip the O(tree) structure-signature walk on the hot path when no
# registration has happened since their executable was traced
_GLOBAL_STRUCTURE_COUNTER = 0


class _OneProgram(threading.local):
    """Entered on a thread while it finishes a block's deferred
    initialisation or traces its ``CachedOp``: every block called meanwhile
    is part of THAT program and runs op by op (``HybridBlock.__call__``), so
    a first call builds, traces and compiles one program and not one more a
    descendant.  A thread's own count, so no descendant's ``_active``,
    ``_flags`` or ``_cached_op`` is touched and a block shared with a
    hybridized parent on another thread is left alone."""

    depth = 0

    def __enter__(self):
        self.depth += 1

    def __exit__(self, *_exc):
        self.depth -= 1


_one_program = _OneProgram()


class Block:
    """Base class for all neural network layers and models (reference
    ``block.py:128``)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._structure_version = 0    # bumped on any child registration

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            [f"  ({key}): {_indent(str(block), 2)}"
             for key, block in self.__dict__.items()
             if isinstance(block, Block)])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Registers parameters and child blocks (reference ``block.py:187``)."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params, \
                "Overriding Parameter attribute %s is not allowed. " \
                "If you want to share parameters between blocks, please set " \
                "'params' at Block construction instead."
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _check_container_with_block(self):
        children = set(self._children.values())

        def _find_unregistered_block_in_container(data):
            if isinstance(data, (list, tuple)):
                for ele in data:
                    if _find_unregistered_block_in_container(ele):
                        return True
                return False
            if isinstance(data, dict):
                for _, v in data.items():
                    if _find_unregistered_block_in_container(v):
                        return True
                return False
            if isinstance(data, Block):
                return data not in children
            return False

        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and not (k.startswith("__") or k == "_children"):
                if _find_unregistered_block_in_container(v):
                    warnings.warn(
                        f'"{k}" is an unregistered container with Blocks. '
                        "Note that Blocks inside the list, tuple or dict will "
                        "not be registered automatically. Make sure to register "
                        "them using register_child() or switching to "
                        "nn.Sequential/nn.HybridSequential instead. ",
                        stacklevel=3)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Name scope managing child naming (reference ``block.py:241``)."""
        return self._scope

    @property
    def params(self):
        """This Block's direct parameter dictionary — does NOT include
        children's (reference ``block.py:270``)."""
        return self._params

    def collect_params(self, select=None):
        """ParameterDict of this Block and all children (reference
        ``block.py:278``)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Save parameters to file in the reference's NDArray-map format
        (reference ``block.py:316``)."""
        params = self._collect_params_with_prefix()
        if deduplicate:
            reverse_params = {v: k for k, v in params.items()}
            params = {v: k for k, v in reverse_params.items()}
        arg_dict = {key: val._reduce() for key, val in params.items()}
        ndarray.save(filename, arg_dict)

    def save_params(self, filename):
        """Deprecated pre-1.4 API (reference ``block.py save_params``):
        saves in the ``collect_params().save`` legacy format."""
        warnings.warn("save_params is deprecated; use save_parameters "
                      "(note the file formats differ)", DeprecationWarning)
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        """Deprecated pre-1.4 API (reference ``block.py load_params``)."""
        warnings.warn("load_params is deprecated; use load_parameters",
                      DeprecationWarning)
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        """Load parameters saved by ``save_parameters`` (reference
        ``block.py:357``)."""
        loaded = ndarray.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in i for i in loaded.keys()) and \
                not (params and (set(params) & set(loaded))):
            # legacy loading: collect_params().save() format.  Dot-free
            # keys that exactly cover this block's structured names are
            # NOT legacy — a bare SymbolBlock has flat names (no child
            # dots) and must round-trip through the structured path.
            del loaded
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix,
                cast_dtype=cast_dtype, dtype_source=dtype_source)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    f"Parameter '{name}' is missing in file '{filename}', " \
                    f"which contains parameters: {list(loaded.keys())[:8]}. " \
                    "Please make sure source and target networks have the " \
                    "same prefix."
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    f"Parameter '{name}' loaded from file '{filename}' is not "
                    "present in ParameterDict, choices are: "
                    f"{list(params.keys())[:8]}. Set ignore_extra=True to "
                    "ignore.")
            if name in params:
                params[name]._load_init(loaded[name], ctx,
                                        cast_dtype=cast_dtype,
                                        dtype_source=dtype_source)

    def register_child(self, block, name=None):
        """Register a child block (reference ``block.py:423``)."""
        global _GLOBAL_STRUCTURE_COUNTER
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        self._structure_version += 1
        _GLOBAL_STRUCTURE_COUNTER += 1

    def _structure_sig(self):
        """Snapshot of the block tree's identity+version — a hybridized
        ANCESTOR compares this against the signature captured when its
        executable was traced, so a structural edit anywhere below
        invalidates the cache (reference CachedOp rebuild-on-mutation)."""
        acc = []
        stack = [self]
        while stack:
            b = stack.pop()
            acc.append((id(b), b._structure_version))
            stack.extend(b._children.values())
        return tuple(acc)

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle._id] = hook
        return handle

    def apply(self, fn):
        """Apply fn recursively to self and children (reference
        ``block.py:468``)."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        """Initialize parameters of self and children (reference
        ``block.py:482``)."""
        from .. import initializer as _init
        init = _init.Uniform() if init is None else init
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Activate graph capture on HybridBlock children (reference
        ``block.py:501``)."""
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        """Cast parameters and gradients (reference ``block.py:515``)."""
        for blk in self._children.values():
            blk.cast(dtype)
        for p in self.params.values():
            p.cast(dtype)

    def __call__(self, *args):
        """Call forward with pre/post hooks (reference ``block.py:539``)."""
        for pre_hook in self._forward_pre_hooks.values():
            pre_hook(self, args)
        out = self.forward(*args)
        for post_hook in self._forward_hooks.values():
            post_hook(self, args, out)
        return out

    def forward(self, *args):
        """Override to implement computation (reference ``block.py:553``)."""
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table by running one forward pass
        with tracing hooks (reference ``block.py:559``; printed format
        kept compatible)."""
        rows = []            # (label, shape_str, n_params, trainable, shared)
        counted = set()      # Parameters already attributed to a layer
        hooks = []

        def _shape_str(x):
            """Mirror the input nesting, replacing arrays by shapes."""
            if isinstance(x, NDArray):
                return str(tuple(x.shape))
            if isinstance(x, (list, tuple)):
                return str([_shape_str(i) for i in x]).replace("'", "")
            return str(x)

        def _trace(block):
            if isinstance(block, HybridBlock) and block._active:
                raise AssertionError(
                    f'"{block.name}" must not be hybridized to print '
                    "summary.")

            def _record(blk, _, outputs):
                total = trainable = shared = 0
                for p in blk.params.values():
                    size = p.data().size
                    total += size
                    if p.grad_req != "null":
                        trainable += size
                    if p in counted:
                        shared += size
                    counted.add(p)
                rows.append((f"{type(blk).__name__}-{len(rows)}",
                             _shape_str(outputs), total, trainable,
                             shared))

            hooks.append(block.register_forward_hook(_record))

        one = inputs[0] if len(inputs) == 1 else list(inputs)
        rows.append(("Input", _shape_str(one), 0, 0, 0))
        try:
            self.apply(_trace)
            self(*inputs)
            fmt = "{:>20}  {:>42} {:>15}".format
            print("-" * 80)
            print(fmt("Layer (type)", "Output Shape", "Param #"))
            print("=" * 80)
            for label, shape, n, _t, _s in rows:
                print(fmt(label, shape, n))
            total = sum(r[2] for r in rows)
            trainable = sum(r[3] for r in rows)
            shared = sum(r[4] for r in rows)
            print("=" * 80)
            print("Parameters in forward computation graph, "
                  "duplicate included")
            print("   Total params: " + str(total))
            print("   Trainable params: " + str(trainable))
            print("   Non-trainable params: " + str(total - trainable))
            print("Shared params in forward computation graph: "
                  + str(shared))
            print("Unique parameters in model: " + str(total - shared))
            print("-" * 80)
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        self._id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1

    def detach(self):
        self._hooks_dict.pop(self._id, None)


class CachedOp:
    """jit-compiled replay of a HybridBlock's forward — the TPU-native
    ``CachedOp`` (reference ``src/imperative/cached_op.cc:904``; here the
    "static plan" is the XLA executable and the jit cache plays the role of
    ``StaticForward``'s reused exec state)."""

    def __init__(self, block, flags=()):
        import jax
        self._block = block
        self._flags = dict(flags)
        self._params = None
        self._aux_params = None
        self._jitted = {}
        # cache_key -> [indices into the aux params of those a forward of
        # that graph CHANGED]: only these come back as outputs and are
        # rebound.  An inference graph changes none, so a served model's
        # weights are not copied out of every call (and held twice).
        self._aux_changed = {}
        self._out_fmt = [None]
        self._jax = jax
        self._seen_sigs = set()   # telemetry: (cache_key, shapes/dtypes)
        # sig -> AOT-compiled executable (serving warm path): a hit replays
        # the XLA binary directly — no trace, no jit-cache lookup miss
        self._aot = {}

    def _collect(self):
        if self._params is None:
            items = sorted(self._block.collect_params().items())
            self._params = [p for _, p in items]
            self._aux_params = [p for p in self._params if p.grad_req == "null"]
        return self._params, self._aux_params

    def _fn_for(self, cache_key, n_in, in_fmt):
        fn = self._jitted.get(cache_key)
        if fn is None:
            changed = self._aux_changed.setdefault(cache_key, [None])
            fn = self._make_fn(cache_key[0], n_in, in_fmt, changed)
            self._jitted[cache_key] = fn
        return fn

    def _make_fn(self, training, n_in, in_fmt, changed):
        params, aux = self._collect()
        block = self._block
        handles = [p.data() for p in params]
        aux_at = [params.index(p) for p in aux]
        out_fmt = self._out_fmt

        def pure(*raw, __key__=None):
            in_raw, par_raw = raw[:n_in], raw[n_in:]
            old = [h._data for h in handles]
            with autograd.pause(train_mode=training), \
                    _rnd.key_scope(__key__), _one_program:
                for h, r in zip(handles, par_raw):
                    h._data = r
                try:
                    wrapped = [ndarray._wrap(r) for r in in_raw]
                    grouped, _ = _regroup(wrapped, in_fmt)
                    out = block.forward(*grouped)
                    flat, fmt = _flatten(out, "output")
                    out_fmt[0] = fmt
                    out_raw = [o._data for o in flat]
                    # auxiliary state this forward wrote (BatchNorm's
                    # moving statistics under training): the handle no
                    # longer holds the argument it was given
                    changed[0] = [j for j, k in enumerate(aux_at)
                                  if handles[k]._data is not par_raw[k]]
                    aux_raw = [handles[aux_at[j]]._data for j in changed[0]]
                finally:
                    for h, o in zip(handles, old):
                        h._data = o
            return tuple(out_raw) + tuple(aux_raw)

        return self._jax.jit(pure)

    def __call__(self, *inputs):
        import jax

        params, aux = self._collect()
        datas = [p.data() for p in params]
        training = autograd.is_training()
        flat_in, in_fmt = _flatten(list(inputs), "input")
        # stage concrete inputs onto the parameters' device — a hybridized
        # block jits over (inputs + params) and XLA requires one platform
        # (e.g. a host-created arange index meeting TPU-resident weights)
        if datas and not isinstance(datas[0]._data, jax.core.Tracer):
            try:
                pdev = list(datas[0]._data.devices())[0]
                for x in flat_in:
                    if not isinstance(x._data, jax.core.Tracer) and \
                            list(x._data.devices())[0] != pdev:
                        x._data = jax.device_put(x._data, pdev)
            except jax.errors.ConcretizationTypeError:
                pass
        # the sequence-parallel scope changes what some layers trace (ring
        # vs local attention) — a graph captured outside the scope must not
        # be replayed inside it
        from ..parallel.sp_context import current_sequence_parallel
        sp = current_sequence_parallel()
        sp_key = None if sp is None else (id(sp[0]),) + tuple(sp[1:])
        cache_key = (training, len(flat_in), repr(in_fmt), sp_key)
        fn = self._fn_for(cache_key, len(flat_in), in_fmt)
        # a recompile is keyed by (cache_key, input shapes/dtypes): jax.jit
        # retraces SILENTLY on a new shape/dtype — the #1 hidden TPU perf
        # killer.  Signatures are tracked even with telemetry off so that
        # enabling the bus mid-run (attach-to-a-running-job) doesn't report
        # already-compiled signatures as fresh recompiles.
        shapes, dtypes = io_signature(flat_in)
        sig = (cache_key, shapes, dtypes)
        fresh_sig = sig not in self._seen_sigs
        if fresh_sig:
            self._seen_sigs.add(sig)
        if _tel.enabled:
            _tel.count("cachedop.calls", block=self._block.name)
            if fresh_sig:
                _tel.count("cachedop.recompiles", block=self._block.name)
                _tel.instant(
                    "cachedop.recompile", block=self._block.name,
                    training=training, shapes=str(shapes),
                    dtypes=str(dtypes), n_inputs=len(flat_in),
                    cached_graphs=len(self._jitted))
            else:
                _tel.count("cachedop.cache_hits")
        # an AOT-installed executable (persistent program cache, serving
        # warm path) replays for this exact signature without touching the
        # jit trace cache; donation/aliasing semantics are baked into the
        # serialized binary.  AOT entries are only ever installed for
        # inference graphs, and the tape never records against them
        # (inference runs under autograd.pause).
        aot = self._aot.get(sig) if not training else None
        key = _rnd.next_key()
        with _tel.span("cachedop.call", block=self._block.name):
            outs = ndarray.invoke_fn(aot if aot is not None else fn,
                                     list(flat_in) + datas,
                                     attrs={"__key__": key})
        if not isinstance(outs, list):
            outs = [outs]
        changed = self._aux_changed[cache_key][0]
        if changed is None:       # an executable stored before the list was
            changed = range(len(aux))
        n_aux = len(changed)
        if n_aux:
            aux_outs = outs[len(outs) - n_aux:]
            outs = outs[:len(outs) - n_aux]
            for j, a in zip(changed, aux_outs):
                aux[j].data()._data = a._data
        ret, _ = _regroup(outs, self._out_fmt[0])
        return ret

    # -------------------------------------------- AOT export / install
    # (persistent program cache: mxnet_tpu.serving.aot.ProgramCache)
    def _aot_sig(self, flat_inputs, in_fmt, training=False):
        """The exact (cache_key, shapes, dtypes) __call__ computes for
        these inputs outside any sequence-parallel scope."""
        cache_key = (training, len(flat_inputs), repr(in_fmt), None)
        shapes, dtypes = io_signature(flat_inputs)
        return (cache_key, shapes, dtypes)

    def aot_compile(self, flat_inputs, in_fmt, training=False):
        """Trace + XLA-compile the graph at these example inputs ahead of
        time, returning ``(sig, compiled, out_fmt)``.  The ``Compiled``
        stage is installed for replay AND is what
        ``serving.aot.ProgramCache`` serializes — the byte-exact
        executable a plain ``__call__`` would have compiled lazily."""
        import numpy as _np
        params, _aux = self._collect()
        datas = [p.data() for p in params]
        sig = self._aot_sig(flat_inputs, in_fmt, training)
        cache_key = sig[0]
        fn = self._fn_for(cache_key, len(flat_inputs), in_fmt)
        raw = [x._materialize() for x in flat_inputs] + \
            [d._data for d in datas]
        # the PRNG key is a dynamic argument of the compiled function —
        # lower against its fixed (2,) uint32 signature; real calls pass
        # the live key stream exactly as the jit path does
        compiled = fn.lower(
            *raw, __key__=_np.zeros((2,), "uint32")).compile()
        self._seen_sigs.add(sig)
        self._aot[sig] = compiled
        return sig, compiled, self._out_fmt[0]

    def aot_install(self, flat_inputs, in_fmt, compiled, out_fmt,
                    training=False, aux_changed=None):
        """Install a deserialized AOT executable for this signature.
        Registers the signature as seen (no recompile is counted, and
        :meth:`HybridBlock.compiled_signatures` includes it) and records
        the output format that tracing would have produced — the loaded
        path never traces."""
        sig = self._aot_sig(flat_inputs, in_fmt, training)
        self._aot[sig] = compiled
        self._seen_sigs.add(sig)
        if self._out_fmt[0] is None:
            self._out_fmt[0] = out_fmt
        holder = self._aux_changed.setdefault(sig[0], [None])
        if holder[0] is None and aux_changed is not None:
            holder[0] = list(aux_changed)
        return sig


class HybridBlock(Block):
    """A Block that supports graph capture via ``hybridize()`` (reference
    ``block.py:679``).  Subclasses implement
    ``hybrid_forward(self, F, x, *args, **params)`` where ``F`` is the op
    namespace (``mx.nd`` eagerly, ``mx.sym`` when traced symbolically) and
    direct parameters arrive as keyword arguments."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cached_op = None
        self._cached_sig = None
        self._cached_counter = -1
        self._active = False
        self._flags = []
        self._in_sig = None

    def register_child(self, block, name=None):
        # structural change (e.g. Sequential.add AFTER hybridize+run)
        # invalidates the traced executable — reference CachedOp rebuilds
        # on graph mutation (gluon/block.py _clear_cached_op call sites)
        super().register_child(block, name)
        self._clear_cached_op()

    def _get_graph(self, *args):
        flat_args, fmt = _flatten(args, "input")
        return self._get_graph_from_sig(len(flat_args), fmt)

    def _get_graph_from_sig(self, n_flat, fmt):
        """Build the symbolic graph from an input *signature* (count +
        nesting format) — no live arrays needed, so export() doesn't have to
        retain the last input batch."""
        from .. import symbol
        self._in_format = fmt
        inputs = [symbol.var(f"data{i}") if n_flat > 1 else
                  symbol.var("data") for i in range(n_flat)]
        grouped_inputs = _regroup(inputs, self._in_format)[0]
        params = {i: j.var() for i, j in self._reg_params.items()}
        with self.name_scope():
            out = self.hybrid_forward(symbol, *([grouped_inputs] if not
                                                isinstance(grouped_inputs, list)
                                                else grouped_inputs), **params)
        out, self._out_format = _flatten(out, "output")
        return inputs, symbol.Group(out)

    def _clear_cached_op(self):
        self._cached_op = None

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = list(kwargs.items())
        self._clear_cached_op()
        if active and (self._forward_hooks or self._forward_pre_hooks):
            warnings.warn(f'"{self.name}" is being hybridized while still '
                          "having forward hook/pre-hook. If it is a child of "
                          "a HybridBlock, the hooks will not take effect.")
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Infer and set parameter shapes from inputs.  Layers with deferrable
        parameters override ``_shape_from_input``; composite blocks propagate
        naturally because each child infers from its own actual input during
        the eager dry-run (the analog of the reference's symbolic
        ``_deferred_infer_shape``, ``block.py:816``)."""
        raise NotImplementedError(
            f"layer {self.name} has deferred-initialized parameters but does "
            "not implement infer_shape; pass explicit in_units/in_channels or "
            "implement infer_shape")

    def infer_type(self, *args):
        for p in self._reg_params.values():
            p.cast(args[0].dtype)

    def _deferred_infer(self, args):
        try:
            self.infer_shape(*args)
        except NotImplementedError:
            raise
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init()

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Export model symbol + params in the reference's dual-file
        checkpoint format (reference ``block.py:876``)."""
        if not self._active or self._cached_op is None:
            raise RuntimeError(
                "Please first call block.hybridize() and then run forward "
                "with this block at least once before calling export.")
        sym_file = "%s-symbol.json" % path
        inputs, out = self._get_graph_from_sig(*self._in_sig)
        out.save(sym_file)
        arg_names = set(out.list_arguments())
        aux_names = set(out.list_auxiliary_states())
        arg_dict = {}
        for name, param in self.collect_params().items():
            if name in arg_names:
                arg_dict["arg:%s" % name] = param._reduce()
            else:
                arg_dict["aux:%s" % name] = param._reduce()
        params_file = "%s-%04d.params" % (path, epoch)
        ndarray.save(params_file, arg_dict)
        return sym_file, params_file

    def forward(self, x, *args):
        """Dispatch: symbolic when given Symbols, else eager ndarray path
        (reference ``block.py:909``)."""
        from .. import symbol as _sym_mod
        from ..symbol import Symbol
        if isinstance(x, NDArray):
            params = {}
            try:
                for name, p in self._reg_params.items():
                    params[name] = p.data()
            except DeferredInitializationError:
                self._deferred_infer((x,) + args)
                params = {name: p.data() for name, p in self._reg_params.items()}
            return self.hybrid_forward(ndarray, x, *args, **params)
        assert isinstance(x, Symbol), \
            f"HybridBlock requires the first argument to forward be either " \
            f"Symbol or NDArray, but got {type(x)}"
        params = {name: p.var() for name, p in self._reg_params.items()}
        with self.name_scope():
            return self.hybrid_forward(_sym_mod, x, *args, **params)

    def __call__(self, *args):
        if self._active and not _one_program.depth:
            try:
                flat_args, in_fmt = _flatten(list(args), "input")
            except AssertionError:
                flat_args = None  # non-array args: fall back to eager path
            if flat_args is not None and flat_args and \
                    all(isinstance(a, NDArray) for a in flat_args):
                return self._call_cached_op(args, flat_args, in_fmt)
        return super().__call__(*args)

    def _call_cached_op(self, args, flat_args, in_fmt):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        op = self._built_cached_op(args)
        self._in_sig = (len(flat_args), in_fmt)
        out = op(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def _built_cached_op(self, args):
        """This block's ``CachedOp``, built here where there is none or a
        descendant's structure changed since it was traced: the one build
        path (a call, ``compile_for``).  Parameters whose shapes were
        deferred are finished by one op-by-op pass of ``forward`` over
        ``args`` in which no descendant builds a cached op of its own
        (``_OneProgram``): each would compile a program nothing calls
        again."""
        if self._cached_op is not None and \
                self._cached_counter != _GLOBAL_STRUCTURE_COUNTER:
            # some block somewhere registered a child: do the real (rare)
            # O(tree) check; on the common unchanged path this branch is
            # never taken
            if self._cached_sig != self._structure_sig():
                self._cached_op = None   # a descendant's structure changed
                if _tel.enabled:
                    _tel.count("cachedop.invalidations", block=self.name)
                    _tel.instant("cachedop.invalidate", block=self.name,
                                 reason="structure_changed")
            else:
                self._cached_counter = _GLOBAL_STRUCTURE_COUNTER
        if self._cached_op is None:
            try:
                for p in self.collect_params().values():
                    p.data()
            except DeferredInitializationError:
                with autograd.pause(), _one_program:
                    self.forward(*args)
            self._cached_op = CachedOp(self, self._flags)
            self._cached_sig = self._structure_sig()
            self._cached_counter = _GLOBAL_STRUCTURE_COUNTER
        return self._cached_op

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to implement computation using ``F`` (reference
        ``block.py:942``)."""
        raise NotImplementedError

    # ----------------------------------------------- shape-keyed AOT entries
    def compile_for(self, *example_inputs, cache=None, cache_key=None):
        """AOT-compile the cached executable for this exact input signature
        (inference mode) and return the shape/dtype signature key.

        ``jax.jit`` retraces silently on every new input shape; a serving
        path cannot afford that mid-traffic.  Warming each expected batch
        shape through here (the CachedOp path — the analog of the reference
        binding a ``CachedOp`` at a static shape) makes steady-state calls
        pure executable replays.  ``mxnet_tpu.serving.ModelRuntime`` warms
        every batch bucket this way at load.

        With a ``cache`` (:class:`mxnet_tpu.serving.aot.ProgramCache`) the
        warm goes through the persistent program store: a valid on-disk
        entry is deserialized and installed (zero trace, zero XLA
        compile); a miss compiles ahead-of-time and commits the
        executable for the next process.  ``cache_key`` names the entry
        (default: derived from the input shapes).
        """
        if not self._active:
            raise RuntimeError(
                f'"{self.name}" must be hybridized before compile_for(); '
                "call hybridize() first")
        if cache is not None:
            sig = self._aot_compile_for(example_inputs, cache, cache_key)
            if sig is not None:
                return sig
        with autograd.pause(train_mode=False):
            self(*example_inputs)
        flat, _ = _flatten(list(example_inputs), "input")
        return io_signature(flat)

    def _aot_compile_for(self, example_inputs, cache, cache_key):
        """compile_for through a ProgramCache.  Returns the signature on
        success, or None when these inputs can't go through the CachedOp
        path (non-array args) — the caller falls back to a plain traced
        warm."""
        try:
            flat, in_fmt = _flatten(list(example_inputs), "input")
        except AssertionError:
            return None
        if not flat or not all(isinstance(a, NDArray) for a in flat):
            return None
        op = self._built_cached_op(example_inputs)
        self._in_sig = (len(flat), in_fmt)
        shapes, dtypes = io_signature(flat)
        if cache_key is None:
            cache_key = "cachedop-" + "_".join(
                "x".join(map(str, s)) or "scalar" for s in shapes)
        hit = cache.load(cache_key)
        if hit is not None:
            fn, extra = hit
            op.aot_install(
                flat, in_fmt, fn, extra.get("out_fmt"),
                aux_changed=extra.get("aux_changed"))
        else:
            sig, compiled, out_fmt = op.aot_compile(flat, in_fmt)
            # an installed executable never traces: what tracing learned
            # of the graph's outputs is stored beside it
            cache.store(cache_key, compiled, extra={
                "out_fmt": out_fmt,
                "aux_changed": op._aux_changed[sig[0]][0]})
        return (shapes, dtypes)

    def compile_grid(self, make_example, buckets, cache=None):
        """AOT-compile a whole bucket *ladder* of signatures in one pass.

        ``buckets`` is an iterable of bucket keys — scalars for a 1-D
        ladder (``serving.ModelRuntime``'s batch buckets) or tuples for a
        multi-dimensional grid (the decode runtime's 2-D *(batch_bucket,
        seq_bucket)* prefill ladder).  ``make_example(*key)`` must return
        the example input list for that bucket; each is warmed through
        :meth:`compile_for`.  Returns ``{bucket_key: signature}`` so the
        caller can keep an O(1) warmed-signature set and assert zero
        steady-state compiles (``serving.compile_miss`` /
        ``decode.compile_miss``).  A ``cache`` routes every bucket through
        the persistent program store (entry ``cachedop-<bucket>``)."""
        sigs = {}
        for bucket in buckets:
            if isinstance(bucket, (tuple, list)):
                bucket = tuple(bucket)
                key = "cachedop-" + "-".join(map(str, bucket))
                sigs[bucket] = self.compile_for(
                    *make_example(*bucket), cache=cache, cache_key=key)
            else:
                sigs[bucket] = self.compile_for(
                    *make_example(bucket), cache=cache,
                    cache_key=f"cachedop-{bucket}")
        return sigs

    def compiled_signatures(self, training=None):
        """Shape/dtype signatures the cached executable has already traced.

        Membership answers "will this input replay a compiled graph or
        trigger a fresh trace?" — the signature key is exactly what
        :meth:`compile_for` returns, so a caller can warm shapes and then
        assert zero steady-state compiles (``serving.compile_miss``).

        The CachedOp cache is keyed by autograd mode as well as shape: a
        shape traced only under ``training=True`` replays NOTHING in
        inference.  ``training=None`` returns every mode's signatures;
        pass ``True``/``False`` to restrict to one mode (serving checks
        must pass ``False``)."""
        if self._cached_op is None:
            return frozenset()
        return frozenset(
            (shapes, dtypes) for key, shapes, dtypes
            in self._cached_op._seen_sigs
            if training is None or key[0] == training)


class SymbolBlock(HybridBlock):
    """Construct a Block from a Symbol (reference ``block.py:960``) — wraps an
    arbitrary symbolic graph so it runs in Gluon; used by ``import`` paths
    (e.g. loading an exported model)."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Import a model exported by ``HybridBlock.export`` (reference
        ``block.py:992``)."""
        from .. import symbol as _sym_mod
        sym = _sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym_mod.var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            params = ndarray.load(param_file)
            remapped = {}
            for k, v in params.items():
                if k.startswith("arg:") or k.startswith("aux:"):
                    k = k[4:]
                remapped[k] = v
            for name, param in ret.collect_params().items():
                if name in remapped:
                    param._load_init(remapped[name], ctx)
                else:
                    raise AssertionError(f"Parameter {name} missing in {param_file}")
        return ret

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        # Reference resets the prefix so parameter names match the symbol's
        # raw argument names (block.py:1030 region) — required for
        # export/imports round-trips.
        self._prefix = ""
        self._params = ParameterDict("", params)
        from .. import symbol as _sym_mod
        from ..symbol import Symbol
        if isinstance(inputs, (Symbol,)):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(outputs, (list, tuple)):
            outputs = _sym_mod.Group(outputs)
        self._output_sym = outputs
        self._input_syms = inputs
        input_names = set()
        for i in inputs:
            assert len(i.list_outputs()) == 1, \
                "Input symbols must be variable, but %s is an output of operators" % str(i)
            input_names.add(i.list_outputs()[0])
        # create parameters for all non-input args (shared from `params` when
        # the name is already present there)
        arg_params = outputs.list_arguments()
        aux_params = outputs.list_auxiliary_states()
        for name in arg_params:
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in aux_params:
            self.params.get(name, grad_req="null", allow_deferred_init=True)
        self._param_names = [n for n in arg_params if n not in input_names] + \
            list(aux_params)
        # register under attribute names (common prefix stripped) so
        # save_parameters/load_parameters see them — reference
        # block.py:1093 does exactly this
        names = list(self._params.keys())
        if names:
            common = names[0]
            for n in names[1:]:
                while not n.startswith(common):
                    common = common[:-1]
            # strip only up to an underscore boundary so no key collapses
            # to '' (a single-param block would otherwise lose its name)
            common = common[:common.rfind("_") + 1] if "_" in common else ""
            self._reg_params = {k[len(common):]: v
                                for k, v in self._params.items()}

    def forward(self, x, *args):
        from ..symbol import Symbol
        if isinstance(x, NDArray):
            flat_args = [x] + list(args)
            env = {}
            for sym, val in zip(self._input_syms, flat_args):
                env[sym.list_outputs()[0]] = val._data
            for pname in self._param_names:
                env[pname] = self.params[pname].data()._data
            fn, _ = self._output_sym._build_fn(autograd.is_training())
            out, aux_updates = fn(env)
            for aname, val in aux_updates.items():
                if aname in self.params:
                    self.params[aname].data()._data = val
            outs = [ndarray._wrap(o) for o in out]
            return outs[0] if len(outs) == 1 else outs
        assert isinstance(x, Symbol)
        return self._output_sym

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
