"""Tape-based autograd over ``jax.vjp``.

Reference being rebuilt: ``python/mxnet/autograd.py`` scopes backed by the C++
imperative tape (``src/imperative/imperative.cc:193 RecordOp``, ``:280
Backward``; thread-local recording/training flags
``include/mxnet/imperative.h:81-96``).

TPU-native redesign: recording attaches an ``AGNode`` to each produced NDArray
(the analog of ``NDArray::entry_``, reference ``include/mxnet/ndarray.h:86``).
``backward`` walks the tape in reverse topological order and computes input
cotangents with ``jax.vjp`` of each op's *pure JAX function* — there are no
hand-registered backward ops (reference ``src/nnvm/gradient.cc:275``); the
reverse transform is JAX's.  Higher-order gradients (``create_graph=True``)
re-enter the imperative invoke path with each pullback expressed as a pure
function of (inputs, head grads), so backward computations land on the tape and
are themselves differentiable — the analog of the reference re-recording
gradient ops (``imperative.cc:412``).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(flag):
    prev = _st().recording
    if flag and not prev:
        # Entering a record scope is a lazy-engine segment boundary: the
        # tape stores concrete raw inputs per op, so anything still pending
        # from an enclosing ``engine.bulk`` scope must materialize first —
        # gradients are then identical with or without bulking.
        from .engine import recorder as _eng_rec
        if _eng_rec.ever_bulked:
            _eng_rec.flush()
    _state.recording = bool(flag)
    return prev


def set_training(flag):
    prev = _st().training
    _state.training = bool(flag)
    return prev


class _Scope:
    def __init__(self, recording=None, training=None):
        self._rec, self._train = recording, training

    def __enter__(self):
        if self._rec is not None:
            self._prev_rec = set_recording(self._rec)
        if self._train is not None:
            self._prev_train = set_training(self._train)
        return self

    def __exit__(self, *a):
        if self._rec is not None:
            set_recording(self._prev_rec)
        if self._train is not None:
            set_training(self._prev_train)


def record(train_mode=True):
    """``with autograd.record():`` — reference ``autograd.py:122``."""
    return _Scope(recording=True, training=train_mode)


def pause(train_mode=False):
    return _Scope(recording=False, training=train_mode)


def train_mode():
    return _Scope(training=True)


def predict_mode():
    return _Scope(training=False)


# ---------------------------------------------------------------------------
# Tape structure
# ---------------------------------------------------------------------------
class AGNode:
    """One recorded op invocation, or a marked variable leaf.

    ``parents[i]`` is the ``(AGNode, out_index)`` that produced input *i*
    (None when that input doesn't require grad).  ``in_nds`` keeps the input
    NDArray handles alive — the analog of the reference buffering saved
    inputs/outputs per ``GetBackwardDependency`` (``imperative.cc:147``).
    """

    __slots__ = ("fn", "attrs", "in_nds", "parents", "n_out", "is_var",
                 "grad_buf", "grad_req", "custom_vjp", "out_avals", "out_tuple")

    def __init__(self, fn=None, attrs=None, in_nds=(), parents=(), n_out=1):
        self.fn = fn
        self.attrs = attrs or {}
        self.in_nds = list(in_nds)
        self.parents = list(parents)
        self.n_out = n_out
        self.is_var = False
        self.grad_buf = None
        self.grad_req = "write"
        self.custom_vjp = None
        self.out_avals = None
        self.out_tuple = n_out > 1


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach gradient buffers (reference ``autograd.py:197`` /
    ``Imperative::MarkVariables`` ``src/imperative/imperative.cc:123``)."""
    if not isinstance(variables, (list, tuple)):
        variables, gradients = [variables], [gradients]
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        node = AGNode(n_out=1)
        node.is_var = True
        node.grad_buf = g
        node.grad_req = req
        v._ag_node = (node, 0)
        v._ag_grad = g


def record_op(fn, attrs, input_ndarrays, raw_inputs, output_ndarrays,
              out_tuple=None):
    """Analog of ``Imperative::RecordOp`` (reference ``imperative.cc:193``)."""
    parents = [getattr(x, "_ag_node", None) for x in input_ndarrays]
    if all(p is None for p in parents):
        return
    node = AGNode(fn=fn, attrs=attrs, in_nds=list(input_ndarrays),
                  parents=parents, n_out=len(output_ndarrays))
    if out_tuple is not None:
        node.out_tuple = out_tuple
    node.out_avals = [_aval_of(o._data) for o in output_ndarrays]
    for i, o in enumerate(output_ndarrays):
        o._ag_node = (node, i)


def _aval_of(x):
    """Shape/dtype abstract value of an array or tracer.  A pending engine
    handle is not a type jax knows: ``ShapeDtypeStruct`` carries the two
    fields the backward pass reads."""
    try:
        return jax.typeof(x)
    except TypeError:
        return jax.ShapeDtypeStruct(x.shape, x.dtype)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------
def _toposort(roots):
    order, seen = [], set()
    stack = [(n, False) for n in roots]
    while stack:
        node, done = stack.pop()
        if done:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for p in node.parents:
            if p is not None and id(p[0]) not in seen:
                stack.append((p[0], False))
    return order  # parents appear before children


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             create_graph=False):
    """Reference: ``autograd.py:243`` → ``Imperative::Backward``
    (``src/imperative/imperative.cc:280``)."""
    from .ndarray.ndarray import NDArray, _wrap

    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, NDArray):
        head_grads = [head_grads]

    # Gradients are carried as NDArrays so that create_graph recording works.
    grads = {}      # id(node) -> [NDArray | None per output]
    node_by_id = {}
    roots = []
    for h, hg in zip(heads, head_grads):
        ent = getattr(h, "_ag_node", None)
        if ent is None:
            raise ValueError(
                "cannot differentiate: head was not computed inside "
                "autograd.record() from arrays with attached gradients")
        node, idx = ent
        node_by_id[id(node)] = node
        roots.append(node)
        g = _wrap(jnp.ones(h.shape, h.dtype)) if hg is None else hg
        slot = grads.setdefault(id(node), [None] * node.n_out)
        slot[idx] = g if slot[idx] is None else _acc(slot[idx], g, create_graph)

    order = _toposort(roots)
    with _Scope(training=train_mode, recording=create_graph):
        for node in reversed(order):
            node_by_id[id(node)] = node
            gouts = grads.get(id(node))
            if gouts is None or node.is_var:
                continue
            gouts = [g if g is not None else _wrap(jnp.zeros(av.shape, av.dtype))
                     for g, av in zip(gouts, node.out_avals or [])]
            gins = _node_vjp(node, gouts, create_graph)
            for parent, g in zip(node.parents, gins):
                if parent is None or g is None:
                    continue
                pnode, pidx = parent
                node_by_id[id(pnode)] = pnode
                slot = grads.setdefault(id(pnode), [None] * pnode.n_out)
                slot[pidx] = g if slot[pidx] is None else _acc(slot[pidx], g, create_graph)

    # Write into marked-variable gradient buffers.
    for nid, slot in grads.items():
        node = node_by_id[nid]
        if not node.is_var or node.grad_buf is None or node.grad_req == "null":
            continue
        g = slot[0]
        if g is None:
            continue
        buf = node.grad_buf
        from .ndarray.sparse import RowSparseNDArray
        if (isinstance(g, RowSparseNDArray) and g.is_compressed()
                and isinstance(buf, RowSparseNDArray)
                and node.grad_req != "add"):
            # keep the gradient compressed end-to-end (O(nnz) memory): the
            # buffer adopts the rows/indices without densifying
            idx, vals = g._rs
            if vals.dtype != buf.dtype:
                vals = vals.astype(buf.dtype)
            buf.adopt_rows(idx, vals, g._rs_shape)
            continue
        gd = g._data.astype(buf.dtype) if g.dtype != buf.dtype else g._data
        if node.grad_req == "add":
            buf._data = buf._data + gd
        else:
            buf._data = gd
        if create_graph:
            buf._ag_node = g._ag_node  # keep grads differentiable


def _acc(a, b, create_graph):
    from .ndarray.ndarray import invoke_fn, _wrap

    if create_graph:
        return invoke_fn(lambda x, y: x + y, [a, b])
    return _wrap(a._data + b._data)


_VJP_CACHE = {}


def _attrs_key(attrs):
    try:
        return tuple(sorted((k, v if not isinstance(v, (list, dict))
                             else repr(v)) for k, v in attrs.items()))
    except TypeError:
        return repr(sorted(attrs.items(), key=lambda kv: kv[0]))


def _node_vjp(node, gout_nds, create_graph):
    """Input cotangents (as NDArrays) for one tape node.

    The per-(fn, attrs) backward is jit-compiled and cached — without this,
    replaying a CachedOp's forward inside ``jax.vjp`` would run op-by-op
    eagerly (ruinous on TPU); with it, one XLA executable per recorded op
    shape (the role of the reference's cached backward graph,
    ``cached_op.cc:1128``)."""
    from .ndarray.ndarray import invoke_fn, _wrap

    if node.custom_vjp is not None:
        return node.custom_vjp(gout_nds)

    # ops can provide a storage-type-changing backward (Embedding
    # sparse_grad → compressed row-sparse weight cotangent, the analog of
    # the reference's kRowSparseStorage backward dispatch)
    sparse_vjp = getattr(node.fn, "_sparse_vjp", None)
    if sparse_vjp is not None and not create_graph:
        sg = node.attrs.get("sparse_grad", False)
        if sg if isinstance(sg, bool) else str(sg).lower() in ("true", "1"):
            return sparse_vjp(node.attrs, node.in_nds, gout_nds)

    fn, attrs = node.fn, dict(node.attrs)
    n_in = len(node.in_nds)
    multi = node.out_tuple

    # array-valued attrs (PRNG keys) become jit ARGUMENTS — as cache-key
    # constants they would force a recompile every step
    static_attrs = {k: v for k, v in attrs.items()
                    if not hasattr(v, "shape")}
    arr_names = tuple(sorted(k for k in attrs if hasattr(attrs[k], "shape")))
    n_arr = len(arr_names)
    key = (id(fn), _attrs_key(static_attrs), arr_names, n_in, multi)
    bwd = _VJP_CACHE.get(key)
    if bwd is None:
        def bwd(*args):
            arr_vals = args[:n_arr]
            xs = args[n_arr:n_arr + n_in]
            gs = args[n_arr + n_in:]
            at = dict(static_attrs)
            at.update(zip(arr_names, arr_vals))
            _, pb = jax.vjp(lambda *zz: fn(*zz, **at), *xs)
            cot = tuple(gs) if multi else gs[0]
            res = pb(cot)
            return tuple(res)
        bwd = jax.jit(bwd)
        _VJP_CACHE[key] = bwd
        if len(_VJP_CACHE) > 4096:  # bound the cache (keyed on live fns)
            _VJP_CACHE.clear()

    arr_vals = [attrs[k] for k in arr_names]
    if create_graph:
        out = invoke_fn(bwd, arr_vals + list(node.in_nds) + list(gout_nds))
        return out if isinstance(out, list) else [out]
    raw = bwd(*arr_vals, *[x._data for x in node.in_nds],
              *[g._data for g in gout_nds])
    return [_wrap(r) for r in raw]


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Functional gradient API (reference ``autograd.py:270``)."""
    from .ndarray.ndarray import NDArray, zeros_like

    single = isinstance(variables, NDArray)
    if isinstance(heads, NDArray):
        heads = [heads]
    if single:
        variables = [variables]

    saved = []
    bufs = []
    for v in variables:
        ent = getattr(v, "_ag_node", None)
        if ent is None or not ent[0].is_var:
            raise ValueError("variables passed to autograd.grad must have "
                             "attached gradients (attach_grad/mark_variables)")
        saved.append((ent[0].grad_buf, ent[0].grad_req))
        b = zeros_like(v)
        bufs.append(b)
        ent[0].grad_buf = b
        ent[0].grad_req = "write"

    backward(heads, head_grads, retain_graph=bool(retain_graph),
             train_mode=train_mode, create_graph=create_graph)

    for v, (old_buf, old_req) in zip(variables, saved):
        ent = v._ag_node
        ent[0].grad_buf = old_buf
        ent[0].grad_req = old_req
    return bufs[0] if single else bufs


class Function:
    """Custom differentiable function (reference ``autograd.py:365``;
    C++ side ``src/c_api/c_api_function.cc``)."""

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return getattr(self, "_saved", ())

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _wrap

        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (tuple, list))
        outs = [outputs] if single else list(outputs)
        if is_recording():
            parents = [getattr(x, "_ag_node", None) for x in inputs]
            if any(p is not None for p in parents):
                node = AGNode(fn=None, attrs={}, in_nds=list(inputs),
                              parents=parents, n_out=len(outs))
                node.out_avals = [_aval_of(o._data) for o in outs]
                func = self

                def custom_vjp(gout_nds):
                    with pause():
                        igrads = func.backward(*gout_nds)
                    if not isinstance(igrads, (tuple, list)):
                        igrads = [igrads]
                    return list(igrads)

                node.custom_vjp = custom_vjp
                for i, o in enumerate(outs):
                    o._ag_node = (node, i)
        return outs[0] if single else outs
