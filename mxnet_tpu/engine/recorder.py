"""Lazy-dispatch segment recorder — the op-bulking half of the reference
async engine (``src/engine/``), rebuilt TPU-native.

Reference semantics being reproduced: the Python thread *pushes* ops to the
dependency engine and only blocks at explicit sync points
(``WaitToRead``/``WaitForAll``); ``Engine::set_bulk_size`` batches pushed ops
so dispatch overhead amortizes.  The XLA-idiomatic equivalent (in the spirit
of LazyTensor / torch-xla's trace-and-fuse eager mode) is to *record* eager
ops instead of executing them: inside a ``bulk`` scope each capturable op
appends a node to the calling thread's :class:`Segment` and returns an
NDArray whose ``_data`` is a :class:`LazyData` pending handle.  The segment
flushes as ONE jitted XLA program — compiled once per
(op-sequence, shapes, dtypes, donation) signature and replayed from a cache
thereafter — whenever the scope exits, the segment reaches the bulk size, or
anything *materializes* a pending value (``asnumpy``/``item``/
``wait_to_read``/bool coercion, an uncapturable op, autograd record entry).

Because a segment snapshots its concrete input buffers at record time (jax
arrays are immutable) and every escape hatch forces a flush, semantics are
identical to per-op eager execution; the only observable difference is
*when* device work happens — exactly the reference engine's contract.

Fallback matrix (the op executes eagerly, flushing the segment first if it
consumes a pending value):

- op not capturable: unhashable / array-valued attrs, in-place optimizer
  updates and BatchNorm aux writeback (``register.py`` passes
  ``bulk=False``), ops whose abstract eval fails (value-dependent output
  shapes), tracer inputs (already inside a jit/scan trace)
- operand not a plain dense ``NDArray`` (sparse, subclasses)
- autograd recording is on (gradients must see concrete tape inputs)
- AMP hook or operand-capture probe installed
- cross-thread pending handles: a thread that consumes another thread's
  pending value forces that segment's flush (segments are lock-guarded)

Telemetry: ``dispatch.segment_compile_miss`` / ``segment_cache_hits`` /
``segments_flushed`` / ``ops_recorded`` / ``ops_fused`` counters and an
``engine.segment_flush`` span per flush — zero compile misses steady-state
is the acceptance contract (``tests/test_engine_bulk.py``, ci ``engine``
stage).
"""
from __future__ import annotations

import os
import sys
import threading
import weakref

import numpy as _np

import jax

from ..analysis import sanitizer as _san
from ..telemetry import bus as _tel

__all__ = ["LazyData", "Segment", "try_record", "flush", "thread_stats",
           "bulk_active", "cache_info", "clear_cache"]


def _env_bulk_default():
    try:
        return max(int(os.environ.get("MXNET_ENGINE_BULK", "0") or 0), 0)
    except ValueError:
        return 0


_ENV_DEFAULT = _env_bulk_default()

# Process-wide latch read by the eager dispatch fast path: until the first
# opt-in (env var or set_bulk_size>0) it stays False and dispatch behavior
# is byte-identical to a build without the recorder.
ever_bulked = _ENV_DEFAULT > 0

# Safety cap on ops per segment regardless of the requested bulk size (a
# huge bulk size must not grow an unbounded program / trace time).
MAX_SEGMENT_OPS = 256

_SEGMENT_CACHE = {}          # (program sig, donate mask) -> jitted program
_SEGMENT_CACHE_CAP = 1024
_ABSTRACT_CACHE = {}         # (fn id, attrs key, in avals) -> (out avals, single)
_ABSTRACT_CACHE_CAP = 8192
_NO_CAPTURE = set()          # id(op.fn) whose abstract eval failed — eager forever


class _State:
    """One thread's engine state, as a PLAIN object: a :class:`Segment`
    captures its owner's ``_State`` at creation, and a flush forced from
    another thread mutates it directly — capturing the ``threading.local``
    wrapper instead would resolve to the *forcing* thread's attributes."""

    __slots__ = ("bulk_size", "segment", "segments_flushed", "ops_fused")

    def __init__(self):
        self.bulk_size = _ENV_DEFAULT
        self.segment = None
        self.segments_flushed = 0
        self.ops_fused = 0


class _TLS(threading.local):
    """Per-thread engine state.  Each thread starts from the env default:
    serving workers / io decode threads never inherit (or clobber) the main
    thread's ``bulk``/``set_bulk_size`` scope.  Attribute access delegates
    to the calling thread's ``_State``."""

    def __init__(self):
        self.state = _State()

    @property
    def bulk_size(self):
        return self.state.bulk_size

    @bulk_size.setter
    def bulk_size(self, v):
        self.state.bulk_size = v

    @property
    def segment(self):
        return self.state.segment

    @segment.setter
    def segment(self, v):
        self.state.segment = v

    @property
    def segments_flushed(self):
        return self.state.segments_flushed

    @property
    def ops_fused(self):
        return self.state.ops_fused


_tls = _TLS()

_ND = None


def _nd_cls():
    global _ND
    if _ND is None:
        from ..ndarray.ndarray import NDArray
        _ND = NDArray
    return _ND


class LazyData:
    """Pending output of a recorded-but-not-yet-flushed segment op.

    Sits where a concrete ``jax.Array`` normally lives (``NDArray._data``).
    Shape/dtype/size come from abstract eval; *any* other use forces the
    owning segment to flush: ``__jax_array__`` (jnp ops and ``jax.jit``
    arguments convert through it), ``__array__`` (numpy), ``__getitem__``,
    arithmetic dunders, and a ``__getattr__`` that delegates everything else
    (``devices()``, ``.at``, ``astype``, ``__dlpack__``, ...) to the
    materialized array.  Unhashable on purpose — the per-op jit cache keys
    attrs by hashability and must never key on a pending handle.
    """

    __slots__ = ("_segment", "_slot", "aval", "value", "__weakref__")

    __hash__ = None

    def __init__(self, segment, slot, aval):
        self._segment = segment
        self._slot = slot
        self.aval = aval
        self.value = None

    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        n = 1
        for d in self.aval.shape:
            n *= int(d)
        return n

    def force(self):
        """Materialize: flush the owning segment (once) and return the
        concrete ``jax.Array``."""
        if self.value is None:
            seg = self._segment
            if seg is not None:
                seg.flush()
        return self.value

    def __jax_array__(self):
        return self.force()

    def __array__(self, dtype=None):
        a = _np.asarray(self.force())
        return a.astype(dtype) if dtype is not None else a

    def __getitem__(self, key):
        return self.force()[key]

    def __len__(self):
        if not self.aval.shape:
            raise TypeError("len() of unsized object")
        return self.aval.shape[0]

    def __repr__(self):
        state = "pending" if self.value is None else "materialized"
        return f"<LazyData {state} {self.aval.shape} {self.aval.dtype}>"

    def __getattr__(self, name):
        # only reached for names not found on the class/slots: delegate to
        # the concrete array (forcing the flush if still pending)
        return getattr(self.force(), name)


def _delegating(name):
    def method(self, *args):
        return getattr(self.force(), name)(*args)
    method.__name__ = name
    return method


for _dunder in ("__add__", "__radd__", "__sub__", "__rsub__", "__mul__",
                "__rmul__", "__truediv__", "__rtruediv__", "__floordiv__",
                "__rfloordiv__", "__mod__", "__rmod__", "__pow__",
                "__rpow__", "__neg__", "__abs__", "__matmul__",
                "__rmatmul__", "__eq__", "__ne__", "__lt__", "__le__",
                "__gt__", "__ge__", "__bool__", "__int__", "__float__",
                "__index__"):
    setattr(LazyData, _dunder, _delegating(_dunder))
del _dunder


class Segment:
    """One recorded op sequence owned by a thread.  Lock-guarded so a
    consumer on another thread can safely force the flush."""

    __slots__ = ("lock", "owner", "nodes", "consts", "const_ids", "slots",
                 "out_refs", "flushed")

    def __init__(self):
        self.lock = threading.RLock()
        self.owner = _tls.state   # the recording thread's plain _State — a
        #                      flush forced from ANOTHER thread must still
        #                      clear the owner's pending pointer (else the
        #                      flushed segment pins its buffers until the
        #                      owner records again) and attribute the stats
        #                      to the owner, not the consumer
        self.nodes = []      # (fn, fn_id, op_name, akey, attrs, in_refs, n_out)
        self.consts = []     # concrete jax.Array external inputs (deduped)
        self.const_ids = {}  # id(buffer) -> index into consts
        self.slots = []      # LazyData per produced output
        self.out_refs = []   # weakref to the wrapping NDArray per slot
        self.flushed = False

    def flush(self):
        with self.lock:
            if self.flushed:
                return
            self.flushed = True
            st = self.owner
            if st.segment is self:
                st.segment = None
            if not self.nodes:
                return
            _execute(self, st)


def _attrs_key(attrs):
    """Hashable signature of an attrs dict, or None (arrays / pending
    handles / lists make attrs uncapturable)."""
    try:
        items = tuple(sorted((k, v) for k, v in attrs.items()))
        hash(items)
        return items
    except TypeError:
        return None


def _abstract_eval(op, fn_id, akey, attrs, in_avals):
    """Output ShapeDtypeStructs (+ single-output flag) for one op at the
    given input avals, via ``jax.eval_shape`` — cached, and a failure
    (value-dependent output shape) permanently blacklists the op."""
    key = (fn_id, akey, tuple((a.shape, a.dtype) for a in in_avals))
    hit = _ABSTRACT_CACHE.get(key)
    if hit is not None:
        return hit
    try:
        res = jax.eval_shape(
            lambda *a, _f=op.fn, _at=dict(attrs): _f(*a, **_at), *in_avals)
    except Exception:
        _NO_CAPTURE.add(fn_id)
        if _tel.enabled:
            _tel.count("dispatch.segment_fallbacks", op=op.name,
                       reason="abstract_eval")
        return None
    single = not isinstance(res, (tuple, list))
    outs = [res] if single else list(res)
    for o in outs:
        if not hasattr(o, "shape") or not hasattr(o, "dtype"):
            _NO_CAPTURE.add(fn_id)
            return None
    val = ([jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs], single)
    if len(_ABSTRACT_CACHE) >= _ABSTRACT_CACHE_CAP:
        _ABSTRACT_CACHE.clear()
    _ABSTRACT_CACHE[key] = val
    return val


def bulk_active():
    return _tls.bulk_size > 0


def try_record(op, nd_inputs, raw, attrs):
    """Append one eager op to the calling thread's segment.

    Returns ``(nd_outs, single)`` with pending NDArray results, or None when
    the op is not capturable (the caller dispatches eagerly; it must force
    any pending inputs itself).
    """
    fn_id = id(op.fn)
    if fn_id in _NO_CAPTURE:
        return None
    nd = _nd_cls()
    for x in nd_inputs:
        if type(x) is not nd:
            return None          # sparse / subclass operands: eager path
    akey = _attrs_key(attrs)
    if akey is None:
        return None
    st = _tls.state
    seg = st.segment
    if seg is None or seg.flushed:
        seg = st.segment = Segment()
    # Pre-pass WITHOUT mutating the segment: resolve each input to a slot
    # of this segment or a concrete array, and abstract-eval the op — a
    # fallback here must leave the segment's signature untouched.
    resolved = []            # ("s", slot) | ("c", concrete array)
    in_avals = []
    for r in raw:
        if type(r) is LazyData:
            if r._segment is seg and r.value is None:
                resolved.append(("s", r._slot))
                in_avals.append(r.aval)
                continue
            r = r.force()    # older / cross-thread pending handle
        if isinstance(r, jax.core.Tracer):
            return None      # already inside a jit/scan trace
        resolved.append(("c", r))
        aval = getattr(r, "aval", None)   # jax arrays carry theirs for free
        if aval is None:                  # host numpy (e.g. a PRNG key)
            aval = jax.ShapeDtypeStruct(r.shape, r.dtype)
        in_avals.append(aval)
    shaped = _abstract_eval(op, fn_id, akey, attrs, in_avals)
    if shaped is None:
        return None
    out_avals, single = shaped
    with seg.lock:
        if seg.flushed:
            # another thread forced this segment between the pre-pass and
            # here; the slot refs are stale — dispatch eagerly instead
            return None
        in_refs = []
        for kind, v in resolved:
            if kind == "s":
                in_refs.append(("s", v))
                continue
            ci = seg.const_ids.get(id(v))
            if ci is None:
                ci = len(seg.consts)
                seg.consts.append(v)
                seg.const_ids[id(v)] = ci
            in_refs.append(("c", ci))
        base = len(seg.slots)
        lazies = [LazyData(seg, base + i, av)
                  for i, av in enumerate(out_avals)]
        seg.nodes.append((op.fn, fn_id, op.name, akey, dict(attrs),
                          tuple(in_refs), len(out_avals)))
        seg.slots.extend(lazies)
        nd_outs = [nd(lz) for lz in lazies]
        seg.out_refs.extend(weakref.ref(o) for o in nd_outs)
        n_nodes = len(seg.nodes)
    if _tel.enabled:
        n = _tel.count("dispatch.op_calls", op=op.name)
        if n % 256 == 0:
            _tel.counter_sample("dispatch.op_calls", n)
        _tel.count("dispatch.ops_recorded")
    if n_nodes >= min(st.bulk_size, MAX_SEGMENT_OPS):
        seg.flush()
    return nd_outs, single


def flush():
    """Flush the calling thread's pending segment (no-op when empty)."""
    seg = _tls.segment
    if seg is not None:
        seg.flush()


def thread_stats():
    """(segments_flushed, ops_fused) totals for the calling thread —
    feeds the ``engine.bulk`` span attrs even with telemetry off."""
    st = _tls
    return st.segments_flushed, st.ops_fused


def cache_info():
    """(n_entries, keys) of the compiled-segment cache (test surface)."""
    return len(_SEGMENT_CACHE), list(_SEGMENT_CACHE)


def clear_cache():
    _SEGMENT_CACHE.clear()
    _ABSTRACT_CACHE.clear()


def _signature(nodes, consts):
    node_sig = tuple((fn_id, akey, in_refs, n_out)
                     for (_fn, fn_id, _name, akey, _attrs, in_refs, n_out)
                     in nodes)
    const_sig = tuple((c.shape, c.dtype) for c in consts)
    return (node_sig, const_sig)


def _donatable(consts, slots):
    """Const indices safe to donate to the jitted program: the buffer's
    only remaining Python reference is the segment's own consts list (no
    live NDArray or user variable can observe it after the call), and its
    shape/dtype matches some program output so XLA can actually reuse the
    allocation.  This catches exactly the rebound-handle chains
    (``w += g`` style) the reference engine served with write-dependencies."""
    out_shapes = {(lz.aval.shape, lz.aval.dtype) for lz in slots}
    donate = []
    for i in range(len(consts)):
        # indexing (no loop variable / enumerate tuple holding the array):
        # refs are exactly the consts list entry + the getrefcount argument
        c_shape_dtype = (consts[i].shape, consts[i].dtype)
        if (c_shape_dtype in out_shapes and sys.getrefcount(consts[i]) == 2
                and isinstance(consts[i], jax.Array)):
            donate.append(i)
    return tuple(donate)


def _live_slots(slots):
    """Indices of slots some consumer can still observe.  A LazyData whose
    only reference is the segment's own slots list (refcount: list entry +
    loop var + getrefcount arg) has provably no NDArray handle or user
    variable left — its buffer would be materialized, allocated and
    rebound for nobody.  Returning only live slots keeps a 64-op chain's
    flush at ~1 output array instead of 64, and lets XLA dead-code-eliminate
    ops that feed nothing observable."""
    # indexing (no loop variable / enumerate tuple holding the object):
    # a dead slot's refs are exactly the slots list entry + the
    # getrefcount argument
    return tuple(i for i in range(len(slots))
                 if sys.getrefcount(slots[i]) > 2)


def _build_program(nodes, donate, live):
    specs = tuple((fn, attrs, in_refs)
                  for (fn, _fn_id, _name, _akey, attrs, in_refs, _n) in nodes)

    def program(*consts):
        vals = []
        for fn, attrs, in_refs in specs:
            ins = [consts[i] if kind == "c" else vals[i]
                   for kind, i in in_refs]
            r = fn(*ins, **attrs)
            if isinstance(r, (tuple, list)):
                vals.extend(r)
            else:
                vals.append(r)
        return [vals[i] for i in live]

    return jax.jit(program, donate_argnums=donate)


def _execute(seg, st):
    """Compile-or-replay one segment and materialize its slots."""
    nodes, consts, slots = seg.nodes, seg.consts, seg.slots
    tel_on = _tel.enabled
    live = _live_slots(slots)
    donate = _donatable(consts, slots)
    key = (_signature(nodes, consts), donate, live)
    fn = _SEGMENT_CACHE.get(key)
    if fn is None:
        fn = _build_program(nodes, donate, live)
        if len(_SEGMENT_CACHE) >= _SEGMENT_CACHE_CAP:
            _SEGMENT_CACHE.clear()
        _SEGMENT_CACHE[key] = fn
        if tel_on:
            _tel.count("dispatch.segment_compile_miss")
            _tel.instant("dispatch.segment_compile", ops=len(nodes),
                         consts=len(consts), donated=len(donate),
                         live=len(live))
    elif tel_on:
        _tel.count("dispatch.segment_cache_hits")
    with _tel.span("engine.segment_flush", ops=len(nodes),
                   consts=len(consts)):
        outs = fn(*consts)
    if _san.donation and donate:
        # _donatable proved these consts unreachable from any NDArray at
        # flush time; poisoning still guards the window where a new alias
        # is minted from a stale raw reference (e.g. C-level caches)
        _san.poison([consts[i] for i in donate],
                    f"engine segment flush ({len(nodes)} ops, "
                    f"{len(donate)} donated consts)")
    out_refs = seg.out_refs
    for i, val in zip(live, outs):
        lz = slots[i]
        lz.value = val
        ndv = out_refs[i]()
        if ndv is not None and ndv._data is lz:
            ndv._data = val     # rebind the live handle to the concrete array
    for lz in slots:
        lz._segment = None      # dead slots stay value=None, unobservable
    st.segments_flushed += 1
    st.ops_fused += len(nodes)
    if tel_on:
        _tel.count("dispatch.segments_flushed")
        _tel.count("dispatch.ops_fused", len(nodes))
