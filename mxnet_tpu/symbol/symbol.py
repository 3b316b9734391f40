"""Symbol: the lazy/declarative graph API over the same op table as ``nd``.

Reference being rebuilt: ``python/mxnet/symbol/`` + the NNVM ``Symbol``/
``Graph`` C++ machinery (``src/nnvm/``, ``src/c_api/c_api_symbolic.cc``) and
the executor bind family (``src/executor/graph_executor.cc:376 Init``,
``c_api_executor.cc:555 SimpleBindEx``).

TPU-native redesign: a Symbol is a pure-Python DAG node referencing ops from
the single op table.  There are no NNVM passes — binding traces the graph into
one JAX function and ``jax.jit`` replaces the whole pass pipeline:
gradient generation (``MXGradient``) ≙ ``jax.vjp``; memory planning
(``MXPlanMemory``) ≙ XLA buffer assignment; shape/type inference ≙
``jax.eval_shape``; op fusion/bulking ≙ XLA fusion.  ``infer_shape`` and the
JSON round-trip survive as *API*, computed from the traced graph.
"""
from __future__ import annotations

import json

import numpy as _np

from ..base import uid
from ..ops import registry as _reg
from ..ops.random_ops import STOCHASTIC_OPS

# Ops with auxiliary-state inputs (position -> aux name suffix); mirrors the
# reference's mutable aux inputs (NDArray aux_states in executor bind).
AUX_INPUTS = {"BatchNorm": {3: "moving_mean", 4: "moving_var"},
              "_contrib_SyncBatchNorm": {3: "moving_mean", 4: "moving_var"}}

# Ops whose behavior depends on is_train (OpContext ctx.is_train in reference)
MODE_DEPENDENT = {"Dropout", "BatchNorm", "RNN", "_contrib_SyncBatchNorm",
                  "_contrib_flash_attention_dropout",
                  "_foreach", "_while_loop", "_cond"}

_SIG_CACHE = {}


def _filter_attrs(op, attrs):
    """Drop generic symbol attributes (ctx_group, __lr_mult__, …) that the
    kernel function doesn't accept — MXNet JSON stores them alongside op
    hyperparameters (the reference strips them in ``legacy_json_util.cc``
    and via dmlc-param 'unknown field' tolerance)."""
    import inspect
    key = id(op.fn)
    sig = _SIG_CACHE.get(key)
    if sig is None:
        params = inspect.signature(op.fn).parameters
        has_var_kw = any(p.kind == inspect.Parameter.VAR_KEYWORD
                         for p in params.values())
        sig = (set(params.keys()), has_var_kw)
        _SIG_CACHE[key] = sig
    names, has_var_kw = sig
    if has_var_kw:
        return attrs
    return {k: v for k, v in attrs.items()
            if k in names or k == "__training__"}


class _Node:
    """One op instantiation in the graph (or a variable if ``op is None``)."""

    __slots__ = ("op", "name", "inputs", "attrs", "num_outputs", "attr_dict",
                 "subgraphs")

    def __init__(self, op, name, inputs, attrs, num_outputs=1, attr_dict=None):
        self.op = op            # OpDef or None for variables
        self.name = name
        self.inputs = inputs    # list[(Symbol-producing _Node, out_index)]
        self.attrs = attrs
        self.num_outputs = num_outputs
        self.attr_dict = attr_dict or {}
        self.subgraphs = None   # control-flow bodies (list[Symbol]) or None


class Symbol:
    """A set of outputs of a graph node (MXNet Symbols are output lists)."""

    def __init__(self, outputs):
        self._outputs = outputs  # list[(_Node, int)]

    # ------------------------------------------------------------- structure
    @property
    def name(self):
        node, idx = self._outputs[0]
        if len(self._outputs) == 1:
            if node.op is None or node.num_outputs == 1:
                return node.name
            return f"{node.name}_output{idx}"
        return None

    def __len__(self):
        return len(self._outputs)

    def __getitem__(self, idx):
        if isinstance(idx, str):
            names = self.list_outputs()
            idx = names.index(idx)
        return Symbol([self._outputs[idx]])

    def __iter__(self):
        for i in range(len(self._outputs)):
            yield self[i]

    def get_internals(self):
        """All intermediate outputs (reference ``Symbol.get_internals``)."""
        outs = []
        for node in self._topo():
            if node.op is None:
                outs.append((node, 0))
            else:
                for i in range(node.num_outputs):
                    outs.append((node, i))
        return Symbol(outs)

    def get_children(self):
        node, _ = self._outputs[0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    def _topo(self):
        seen, order = set(), []

        def visit(node):
            if id(node) in seen:
                return
            seen.add(id(node))
            for (p, _i) in node.inputs:
                visit(p)
            order.append(node)

        for (n, _i) in self._outputs:
            visit(n)
        return order

    # ---------------------------------------------------------------- listing
    def _schema_aux_ids(self):
        """Variables that sit at an op's mutable-input positions IN THIS
        GRAPH (reference NNVM mutable-inputs semantics: aux-ness is the op
        schema's call, computed per graph — never stored on shared nodes)."""
        aux = set()
        for node in self._topo():
            if node.op is None:
                continue
            for pos in AUX_INPUTS.get(node.op.name, ()):
                if pos < len(node.inputs) and node.inputs[pos][0].op is None:
                    aux.add(id(node.inputs[pos][0]))
        return aux

    def list_arguments(self):
        aux_ids = self._schema_aux_ids()
        args = []
        for node in self._topo():
            if node.op is None and not node.attr_dict.get("__aux__") \
                    and id(node) not in aux_ids:
                args.append(node.name)
        return args

    def list_outputs(self):
        names = []
        for (node, idx) in self._outputs:
            if node.op is None:
                names.append(node.name)
            elif node.num_outputs == 1:
                names.append(node.name + "_output")
            else:
                names.append(f"{node.name}_output{idx}")
        return names

    def list_auxiliary_states(self):
        aux_ids = self._schema_aux_ids()
        auxs = []
        for node in self._topo():
            if node.op is None and (node.attr_dict.get("__aux__")
                                    or id(node) in aux_ids):
                auxs.append(node.name)
        return auxs

    def list_attr(self):
        return dict(self._outputs[0][0].attr_dict)

    def attr(self, key):
        return self._outputs[0][0].attr_dict.get(key)

    def attr_dict(self):
        out = {}
        for node in self._topo():
            d = {k: v for k, v in node.attr_dict.items() if not k.startswith("__")}
            d.update({k: str(v) for k, v in (node.attrs or {}).items()})
            if d:
                out[node.name] = d
        return out

    def _set_attr(self, **kwargs):
        self._outputs[0][0].attr_dict.update(kwargs)

    # ------------------------------------------------------------- inference
    def infer_shape(self, *args, **kwargs):
        """Shape inference via ``jax.eval_shape`` (replaces the reference's
        InferShape pass, src/executor/infer_graph_attr_pass.cc)."""
        import jax

        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        shapes = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    shapes[n] = s
        shapes.update({k: v for k, v in kwargs.items() if v is not None})

        # aux shapes are derivable once args are known: trace with structs
        known = dict(shapes)
        # iterate: infer aux from the op attrs is hard generically; require
        # caller to give data shapes and propagate
        try:
            specs = self._make_arg_specs(known)
        except KeyError as e:
            return None, None, None
        fn, all_names = self._build_fn(is_train=False, with_aux_updates=False)
        out = jax.eval_shape(lambda kv: fn(kv), {n: specs[n] for n in all_names})
        out_shapes = [tuple(o.shape) for o in out]
        arg_shapes = [tuple(specs[n].shape) for n in arg_names]
        aux_shapes = [tuple(specs[n].shape) for n in aux_names]
        return arg_shapes, out_shapes, aux_shapes

    def infer_shape_partial(self, *args, **kwargs):
        try:
            return self.infer_shape(*args, **kwargs)
        except Exception:
            return None, None, None

    def infer_type(self, *args, **kwargs):
        """Type inference (reference ``c_api_symbolic.cc:571``
        MXSymbolInferType): a bidirectional fixpoint pass over per-op
        dtype rules (``symbol/dtype_infer.py`` ≙ the per-op FInferType
        registrations — ElemwiseType unification by default, dedicated
        rules for dtype-forcing ops like Cast/amp_cast/quantize/Embedding
        and mixed-dtype signatures like BatchNorm).  Dtypes that remain
        unconstrained after the fixpoint default to float32, the
        reference executor's default for unannotated variables."""
        t, by_name = self._run_type_pass(args, kwargs)
        f32 = _np.dtype(_np.float32)
        arg_types = [by_name.get(n) or f32 for n in self.list_arguments()]
        aux_types = [by_name.get(n) or f32
                     for n in self.list_auxiliary_states()]
        out_types = [t[(id(n), i)] or f32 for (n, i) in self._outputs]
        return arg_types, out_types, aux_types

    def infer_type_partial(self, *args, **kwargs):
        """Partial type inference (reference ``infer_type_partial``):
        like ``infer_type`` but leaves unconstrained slots as ``None``
        instead of defaulting, and never raises on conflicts."""
        t, by_name = self._run_type_pass(args, kwargs,
                                         raise_on_conflict=False)
        arg_types = [by_name.get(n) for n in self.list_arguments()]
        aux_types = [by_name.get(n) for n in self.list_auxiliary_states()]
        out_types = [t[(id(n), i)] for (n, i) in self._outputs]
        return arg_types, out_types, aux_types

    def _run_type_pass(self, args, kwargs, raise_on_conflict=True):
        """Returns (tensor-key dtype map, {variable name: dtype})."""
        from .dtype_infer import infer_dtypes, parse_dtype
        arg_names = self.list_arguments()
        var_nodes = {n.name: n for n in self._topo() if n.op is None}
        given = {}
        for n, ty in zip(arg_names, args):
            if ty is not None:
                given[n] = parse_dtype(ty)
        for k, v in kwargs.items():
            if v is None:
                continue
            if k not in var_nodes:
                raise ValueError(
                    "infer_type keyword %r matches no variable in this "
                    "symbol (arguments: %s)" % (k, arg_names))
            given[k] = parse_dtype(v)
        t = infer_dtypes(self, given, raise_on_conflict=raise_on_conflict)
        by_name = {name: t[(id(node), 0)]
                   for name, node in var_nodes.items()}
        return t, by_name

    def _make_arg_specs(self, shapes, dtypes=None):
        """Resolve ShapeDtypeStructs for every variable, inferring parameter
        shapes the way the reference's InferShape pass does
        (``src/executor/infer_graph_attr_pass.cc``): walk the graph in topo
        order, fill in each layer's weight/bias/aux shapes from its op attrs
        + known input shapes, and shape-evaluate each node via
        ``jax.eval_shape``."""
        import jax

        dtypes = dtypes or {}
        specs = {}          # variable name -> ShapeDtypeStruct
        out_specs = {}      # (id(node), out_idx) -> ShapeDtypeStruct

        def var_spec(name, shape, dtype=None):
            if dtype is not None:
                try:
                    dtype = _np.dtype(dtype)
                except TypeError:
                    dtype = None       # legacy str(dtype) class-repr forms
            s = jax.ShapeDtypeStruct(
                tuple(int(x) for x in shape),
                dtype or _np.dtype(dtypes.get(name, _np.float32)))
            specs[name] = s
            return s

        def eval_node(node):
            in_specs = []
            for p, i in node.inputs:
                s = out_specs.get((id(p), i))
                if s is None:
                    raise KeyError(p.name)
                in_specs.append(s)
            attrs = _filter_attrs(node.op, dict(node.attrs))
            if node.op.name in MODE_DEPENDENT:
                attrs["__training__"] = False
            if node.op.name in STOCHASTIC_OPS or node.op.name == "Dropout":
                key = jax.random.PRNGKey(0)
                outs = jax.eval_shape(
                    lambda *a, _at=attrs, _op=node.op, _k=key:
                        _op.fn(_k, *a, **_at), *in_specs)
            else:
                outs = jax.eval_shape(
                    lambda *a, _at=attrs, _op=node.op: _op.fn(*a, **_at),
                    *in_specs)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            for i, o in enumerate(outs):
                out_specs[(id(node), i)] = jax.ShapeDtypeStruct(
                    tuple(o.shape), o.dtype)

        pending = []
        for node in self._topo():
            if node.op is None:
                vdt = node.attr_dict.get("__dtype__") or None
                if node.name in shapes:
                    out_specs[(id(node), 0)] = var_spec(
                        node.name, shapes[node.name], vdt)
                elif node.attr_dict.get("__shape__"):
                    # a Variable declared with a fully-known shape (gluon
                    # param vars carry theirs through export); partial
                    # shapes (None/0 dims) stay with consumer inference
                    import ast
                    shp = ast.literal_eval(node.attr_dict["__shape__"])
                    if shp is not None and all(isinstance(x, int) and x > 0
                                               for x in shp):
                        # () is a valid scalar declaration
                        out_specs[(id(node), 0)] = var_spec(node.name, shp,
                                                            vdt)
                # else: leave unknown — may be inferable at a consumer
                continue
            pending.append(node)
        # fixpoint sweeps: a layer node can name the shape of a parameter
        # variable sitting *behind* shape-preserving ops (e.g. the
        # quantize→dequantize chains the INT8 rewrite inserts), which
        # unblocks those earlier nodes on the next sweep.
        progress = True
        while pending and progress:
            progress = False
            still = []
            for node in pending:
                _infer_layer_param_shapes(node, out_specs, var_spec)
                try:
                    eval_node(node)
                    progress = True
                except KeyError:
                    still.append(node)
            pending = still
        if pending:
            raise KeyError(pending[0].inputs[0][0].name)
        return specs

    # ------------------------------------------------------------ build/exec
    def _build_fn(self, is_train, with_aux_updates=True):
        """Build a pure function ``fn({name: array}) -> [outputs]`` (+ aux
        updates when requested).  This is the single trace that replaces the
        reference's GraphExecutor::Init pass pipeline."""
        import jax

        order = self._topo()
        var_names = [n.name for n in order if n.op is None]

        def fn(env, rng_key=None):
            vals = {}  # id(node) -> tuple of outputs
            aux_updates = {}
            key = rng_key
            for node in order:
                if node.op is None:
                    vals[id(node)] = (env[node.name],)
                    continue
                ins = [vals[id(p)][i] for (p, i) in node.inputs]
                attrs = _filter_attrs(node.op, dict(node.attrs))
                if node.op.name in MODE_DEPENDENT:
                    attrs["__training__"] = is_train
                if node.op.name in STOCHASTIC_OPS or node.op.name == "Dropout":
                    if key is None:
                        import jax.numpy as jnp
                        k = jax.random.PRNGKey(0)
                    else:
                        key, k = jax.random.split(key)
                    ins = [k] + ins
                out = node.op.fn(*ins, **attrs)
                if not isinstance(out, (tuple, list)):
                    out = (out,)
                if node.op.name in AUX_INPUTS and is_train and with_aux_updates:
                    from ..base import parse_bool, parse_float
                    if not parse_bool(node.attrs.get("use_global_stats", False)):
                        mom = parse_float(node.attrs.get("momentum", 0.9), 0.9)
                        for pos, suffix in AUX_INPUTS[node.op.name].items():
                            pnode, pidx = node.inputs[pos]
                            new_stat = out[1] if suffix == "moving_mean" else out[2]
                            old = vals[id(pnode)][pidx]
                            aux_updates[pnode.name] = mom * old + (1 - mom) * \
                                new_stat.astype(old.dtype)
                vals[id(node)] = tuple(out)
            outputs = [vals[id(n)][i] for (n, i) in self._outputs]
            if with_aux_updates:
                return outputs, aux_updates
            return outputs

        return fn, var_names

    # ------------------------------------------------------------------ bind
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    stype_dict=None, group2ctx=None, shared_arg_names=None,
                    shared_exec=None, shared_buffer=None, **kwargs):
        """Allocate arrays and bind (reference ``c_api_executor.cc:555``)."""
        from ..executor import Executor
        from ..ndarray import zeros

        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        shapes = dict(kwargs)
        arg_shapes, _, aux_shapes = self.infer_shape(**shapes)
        if arg_shapes is None:
            raise ValueError("cannot infer shapes from the provided inputs; "
                             f"need shapes for {arg_names}")
        type_dict = type_dict or {}
        args = {n: zeros(s, ctx=ctx, dtype=type_dict.get(n, _np.float32))
                for n, s in zip(arg_names, arg_shapes)}
        auxs = {n: zeros(s, ctx=ctx) for n, s in zip(aux_names, aux_shapes)}
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(arg_names, grad_req))
        else:
            reqs = dict(grad_req)
        grads = {n: zeros(s, ctx=ctx) for n, s in zip(arg_names, arg_shapes)
                 if reqs.get(n, "write") != "null"}
        return Executor(self, ctx, args, grads, reqs, auxs)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        """Reference ``Executor::Bind`` (include/mxnet/executor.h)."""
        from ..executor import Executor
        from ..ndarray import zeros

        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        args_grad = args_grad or {}
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        aux_states = aux_states or {}
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(arg_names, grad_req))
        else:
            reqs = dict(grad_req)
        for n in aux_names:
            if n not in aux_states:
                shape = None
                raise ValueError(f"aux state {n} must be provided to bind")
        return Executor(self, ctx, dict(args), dict(args_grad), reqs,
                        dict(aux_states))

    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx=ctx, args=kwargs, grad_req="null")
        return ex.forward(is_train=False)

    # ------------------------------------------------------------- serialize
    def tojson(self):
        """MXNet-compatible graph JSON (reference ``MXSymbolSaveToJSON``,
        src/c_api/c_api_symbolic.cc:465)."""
        order = self._topo()
        node_index = {id(n): i for i, n in enumerate(order)}
        nodes = []
        arg_nodes = []
        for i, node in enumerate(order):
            if node.op is None:
                arg_nodes.append(i)
                # dunder attrs (__shape__/__dtype__/__init__) are part of
                # the reference JSON contract; only the internal aux marker
                # stays out (aux-ness is recomputed from the op schema)
                nodes.append({"op": "null", "name": node.name,
                              "attrs": {k: str(v) for k, v in node.attr_dict.items()
                                        if k != "__aux__"},
                              "inputs": []})
            else:
                spec = {
                    "op": node.op.name,
                    "name": node.name,
                    "attrs": {k: str(v) for k, v in node.attrs.items()},
                    "inputs": [[node_index[id(p)], idx, 0] for (p, idx) in node.inputs],
                }
                if node.subgraphs:
                    # control-flow bodies serialize as nested graphs (the
                    # reference's node-level subgraph mechanism)
                    spec["subgraphs"] = [json.loads(sg.tojson())
                                         for sg in node.subgraphs]
                nodes.append(spec)
        heads = [[node_index[id(n)], i, 0] for (n, i) in self._outputs]
        return json.dumps({"nodes": nodes, "arg_nodes": arg_nodes,
                           "node_row_ptr": list(range(len(nodes) + 1)),
                           "heads": heads,
                           "attrs": {"mxnet_version": ["int", 10500]}}, indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ------------------------------------------------------------ arithmetic
    def __add__(self, other):
        return _binary_sym("broadcast_add", "_plus_scalar", self, other)

    def __radd__(self, other):
        return _binary_sym("broadcast_add", "_plus_scalar", self, other)

    def __sub__(self, other):
        return _binary_sym("broadcast_sub", "_minus_scalar", self, other)

    def __rsub__(self, other):
        if isinstance(other, Symbol):
            return other.__sub__(self)
        return _scalar_sym("_rminus_scalar", self, other)

    def __mul__(self, other):
        return _binary_sym("broadcast_mul", "_mul_scalar", self, other)

    def __rmul__(self, other):
        return _binary_sym("broadcast_mul", "_mul_scalar", self, other)

    def __truediv__(self, other):
        return _binary_sym("broadcast_div", "_div_scalar", self, other)

    def __rtruediv__(self, other):
        if isinstance(other, Symbol):
            return other.__truediv__(self)
        return _scalar_sym("_rdiv_scalar", self, other)

    def __pow__(self, other):
        return _binary_sym("broadcast_power", "_power_scalar", self, other)

    def __neg__(self):
        return _scalar_sym("_mul_scalar", self, -1.0)

    # comparisons (reference symbol.py __gt__/__ge__/... → broadcast ops;
    # outputs are 0/1 symbols)
    def __gt__(self, other):
        return _binary_sym("broadcast_greater", "_greater_scalar", self, other)

    def __ge__(self, other):
        return _binary_sym("broadcast_greater_equal", "_greater_equal_scalar",
                           self, other)

    def __lt__(self, other):
        return _binary_sym("broadcast_lesser", "_lesser_scalar", self, other)

    def __le__(self, other):
        return _binary_sym("broadcast_lesser_equal", "_lesser_equal_scalar",
                           self, other)

    def __eq__(self, other):
        if isinstance(other, (Symbol, int, float)):
            return _binary_sym("broadcast_equal", "_equal_scalar", self, other)
        return NotImplemented

    def __ne__(self, other):
        if isinstance(other, (Symbol, int, float)):
            return _binary_sym("broadcast_not_equal", "_not_equal_scalar",
                               self, other)
        return NotImplemented

    def __hash__(self):
        return id(self)

    def __bool__(self):
        # __eq__ returns a graph node, so Python truthiness (membership
        # tests, `if sym:`) would silently misbehave — fail loudly instead
        # (same guard numpy/jax arrays use for ambiguous truth values)
        raise TypeError(
            "The truth value of a Symbol is ambiguous (comparisons build "
            "graph nodes); use explicit ops or identity checks instead")

    def __repr__(self):
        name = self.name
        return f"<Symbol {name if name else 'Grouped'}>"

    # method shortcuts mirroring NDArray
    def reshape(self, shape):
        return _invoke_sym(_reg.require("reshape"), [self], {"shape": shape})

    def astype(self, dtype):
        return _invoke_sym(_reg.require("cast"), [self], {"dtype": str(dtype)})

    def sum(self, axis=None, keepdims=False):
        return _invoke_sym(_reg.require("sum"), [self],
                           {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return _invoke_sym(_reg.require("mean"), [self],
                           {"axis": axis, "keepdims": keepdims})

    def transpose(self, axes=None):
        return _invoke_sym(_reg.require("transpose"), [self], {"axes": axes})


# ---------------------------------------------------------------------------
# Construction helpers
# ---------------------------------------------------------------------------
def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, stype=None, **kwargs):
    """Reference ``mx.sym.Variable``."""
    from ..attribute import current as _attr_current
    ad = dict(_attr_current().get(dict(attr or {})))
    if shape is not None:
        ad["__shape__"] = str(tuple(shape))
    if dtype is not None:
        try:
            ad["__dtype__"] = _np.dtype(dtype).name
        except TypeError:
            ad["__dtype__"] = str(dtype)
    if lr_mult is not None:
        ad["lr_mult"] = str(lr_mult)
    if wd_mult is not None:
        ad["wd_mult"] = str(wd_mult)
    if init is not None:
        ad["__init__"] = init if isinstance(init, str) else init.dumps()
    node = _Node(None, name, [], {}, 1, ad)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols):
    outs = []
    for s in symbols:
        outs.extend(s._outputs)
    return Symbol(outs)


def load_json(json_str):
    """Rebuild a Symbol from MXNet graph JSON — current format and the
    legacy pre-1.0 one (2-element input entries, ``attr``/``param`` keys;
    the reference upgrades these in ``src/nnvm/legacy_json_util.cc``)."""
    g = json.loads(json_str)

    def entry(e):
        return (e[0], e[1])  # (node_id, out_idx); v3 adds a version field

    nodes = []
    for spec in g["nodes"]:
        # legacy nodes may carry both "param" (op hyperparameters) and
        # "attr" (generic attributes); the modern format merges as "attrs"
        attrs = {}
        attrs.update(spec.get("param") or {})
        attrs.update(spec.get("attr") or {})
        attrs.update(spec.get("attrs") or {})
        if spec["op"] == "null":
            node = _Node(None, spec["name"], [], {}, 1, attrs)
        elif spec.get("subgraphs"):
            # control-flow node: rebuild body symbols and the lax kernel
            from . import contrib_ctrl
            inputs = [(nodes[i], oi) for (i, oi) in map(entry, spec["inputs"])]
            subs = [load_json(json.dumps(sg)) for sg in spec["subgraphs"]]
            node = contrib_ctrl.rebuild_ctrl_node(
                spec["op"], spec["name"], attrs, inputs, subs)
        else:
            op = _reg.get(spec["op"])
            if op is None:
                raise ValueError(f"unknown op in JSON: {spec['op']}")
            inputs = [(nodes[i], oi) for (i, oi) in map(entry, spec["inputs"])]
            node = _Node(op, spec["name"], inputs, attrs,
                         _num_outputs_of(op, attrs, len(inputs)))
            # fix num_outputs for known multi-output ops
            if op.name in AUX_INPUTS:
                if len(inputs) == 3:
                    # legacy graphs omit aux-state inputs; the reference
                    # appends them on load (legacy_json_util.cc).  NOTE:
                    # the synthesized vars must NOT join ``nodes`` — that
                    # list mirrors the JSON numbering used by input refs.
                    for suffix in ("moving_mean", "moving_var"):
                        aux = _Node(None, f"{spec['name']}_{suffix}", [], {},
                                    1, {"__aux__": "1"})
                        inputs.append((aux, 0))
                else:
                    # aux-ness comes from the op schema (mutable inputs in
                    # the reference), not the JSON — re-mark the vars at
                    # the aux positions so list_auxiliary_states is right
                    for pos in AUX_INPUTS[op.name]:
                        if pos < len(inputs) and inputs[pos][0].op is None:
                            inputs[pos][0].attr_dict["__aux__"] = "1"
                node.num_outputs = 3
        nodes.append(node)
    heads = [(nodes[i], oi) for (i, oi) in map(entry, g["heads"])]
    return Symbol(heads)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


# ---------------------------------------------------------------------------
# Op function generation for the sym namespace
# ---------------------------------------------------------------------------
_NAME_COUNTER = {}


def _auto_name(opname):
    base = opname.lower().lstrip("_")
    c = _NAME_COUNTER.get(base, 0)
    _NAME_COUNTER[base] = c + 1
    return f"{base}{c}"


def _num_outputs_of(op, attrs, n_inputs):
    from ..base import parse_bool, parse_int

    if op.name in AUX_INPUTS:
        # These ops compute (out, mean, var) but only `out` is composable —
        # matching the reference's num_visible_outputs=1 for BatchNorm.
        return 1
    if op.name in ("split", "SliceChannel"):
        return parse_int(attrs.get("num_outputs", 1), 1)
    if op.name == "split_v2":
        sections = parse_int(attrs.get("sections", 0), 0)
        if sections:
            return sections
        from ..base import parse_tuple
        return len(parse_tuple(attrs.get("indices", ()))) + 1
    if op.name in ("_linalg_slogdet", "moments", "_linalg_gelqf", "_linalg_syevd"):
        return 2
    if op.name in ("_contrib_quantize", "_contrib_quantize_v2",
                   "_contrib_requantize"):
        return 3
    if op.name == "RNN":
        if parse_bool(attrs.get("state_outputs", False)):
            return 3 if attrs.get("mode", "lstm") == "lstm" else 2
        return 1
    if op.name == "topk" and attrs.get("ret_typ") == "both":
        return 2
    if op.name == "_contrib_MultiBoxTarget":
        return 3
    if op.name == "histogram":
        return 2
    if op.name == "amp_multicast":
        return max(parse_int(attrs.get("num_outputs", n_inputs)), 1)
    if op.name == "Custom":
        from ..operator import _REGISTRY, _prop_for
        try:
            prop = _prop_for(attrs.get("op_type"), attrs)
            return max(len(prop.list_outputs()), 1)
        except Exception:
            return 1
    return 1


def _invoke_sym(op, sym_inputs, attrs, name=None):
    inputs = []
    for s in sym_inputs:
        if not isinstance(s, Symbol):
            raise TypeError(f"symbol op {op.name} requires Symbol inputs, got {type(s)}")
        inputs.extend(s._outputs)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    nm = name or _auto_name(op.name)
    node = _Node(op, nm, inputs, attrs,
                 _num_outputs_of(op, attrs, len(inputs)))
    return Symbol([(node, i) for i in range(node.num_outputs)]) \
        if node.num_outputs > 1 else Symbol([(node, 0)])


def _scalar_sym(opname, s, scalar):
    return _invoke_sym(_reg.require(opname), [s], {"scalar": float(scalar)})


def _binary_sym(opname, scalar_opname, lhs, rhs):
    if isinstance(lhs, Symbol) and isinstance(rhs, Symbol):
        return _invoke_sym(_reg.require(opname), [lhs, rhs], {})
    if isinstance(lhs, Symbol):
        return _scalar_sym(scalar_opname, lhs, rhs)
    return _scalar_sym(scalar_opname, rhs, lhs)


def make_sym_func(op):
    from ..ndarray.register import _attr_param_names

    attr_names = _attr_param_names(op, op.name in STOCHASTIC_OPS)

    def fn(*args, name=None, attr=None, **kwargs):
        sym_inputs = []
        i = 0
        while i < len(args) and isinstance(args[i], Symbol):
            sym_inputs.append(args[i])
            i += 1
        attrs = {}
        for v, pname in zip(args[i:], attr_names):
            attrs.setdefault(pname, v)
        # separate Symbol kwargs (named inputs like data=, weight=) from attrs
        named_inputs = {}
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                named_inputs[k] = v
            else:
                attrs[k] = v
        auto = name if name is not None else _auto_name(op.name)
        from ..attribute import current as _attr_current
        node_attr = _attr_current().get(dict(attr or {}))

        def _finish(res):
            if node_attr:
                res._outputs[0][0].attr_dict.update(node_attr)
            return res

        if op.name in LAYER_INPUTS:
            # layer-like op: fixed input list; auto-create missing weight/aux
            # variables named `<opname>_<slot>` (the reference's ListArguments
            # + simple_bind deferred allocation behavior)
            order = LAYER_INPUTS[op.name](attrs)
            supplied = dict(zip(order, sym_inputs))
            supplied.update(named_inputs)
            ins = []
            for k in order:
                if k not in supplied:
                    v = Variable(f"{auto}_{k}")
                    if k in AUX_INPUTS_BY_NAME.get(op.name, ()):
                        v._outputs[0][0].attr_dict["__aux__"] = True
                    supplied[k] = v
                ins.append(supplied[k])
            return _finish(_invoke_sym(op, ins, attrs, name=auto))
        if named_inputs:
            order = _input_order(op, named_inputs)
            return _finish(_invoke_sym(
                op, sym_inputs + [named_inputs[k] for k in order],
                attrs, name=auto))
        return _finish(_invoke_sym(op, sym_inputs, attrs, name=auto))

    fn.__name__ = op.name
    fn.__doc__ = op.doc
    return fn


# Named-input declarations for layer-like ops (reference: each op's
# ``ListArguments`` — e.g. FullyConnected lists data/weight/bias).
def _fc_inputs(attrs):
    from ..base import parse_bool
    return ["data", "weight"] if parse_bool(attrs.get("no_bias", False)) \
        else ["data", "weight", "bias"]


def _conv_inputs(attrs):
    from ..base import parse_bool
    return ["data", "weight"] if parse_bool(attrs.get("no_bias", False)) \
        else ["data", "weight", "bias"]


def _deconv_inputs(attrs):
    from ..base import parse_bool
    return ["data", "weight"] if parse_bool(attrs.get("no_bias", True)) \
        else ["data", "weight", "bias"]


LAYER_INPUTS = {
    "FullyConnected": _fc_inputs,
    "Convolution": _conv_inputs,
    "Deconvolution": _deconv_inputs,
    "BatchNorm": lambda a: ["data", "gamma", "beta", "moving_mean", "moving_var"],
    "_contrib_SyncBatchNorm": lambda a: ["data", "gamma", "beta",
                                         "moving_mean", "moving_var"],
    "LayerNorm": lambda a: ["data", "gamma", "beta"],
    "InstanceNorm": lambda a: ["data", "gamma", "beta"],
    "Embedding": lambda a: ["data", "weight"],
    "RNN": lambda a: (["data", "parameters", "state", "state_cell"]
                      if str(a.get("mode", "lstm")) == "lstm"
                      else ["data", "parameters", "state"]),
    "LeakyReLU": lambda a: (["data", "gamma"] if a.get("act_type") == "prelu"
                            else ["data"]),
    "SoftmaxOutput": lambda a: ["data", "label"],
    "LinearRegressionOutput": lambda a: ["data", "label"],
    "LogisticRegressionOutput": lambda a: ["data", "label"],
    "MAERegressionOutput": lambda a: ["data", "label"],
    "SVMOutput": lambda a: ["data", "label"],
}

AUX_INPUTS_BY_NAME = {"BatchNorm": {"moving_mean", "moving_var"},
                      "_contrib_SyncBatchNorm": {"moving_mean", "moving_var"}}


def _infer_layer_param_shapes(node, out_specs, var_spec):
    """Fill unknown variable-input shapes of a layer node from op attrs —
    the per-op shape rules of the reference's FInferShape registrations
    (e.g. FullyConnected weight = (num_hidden, in_features),
    src/operator/nn/fully_connected.cc)."""
    from ..base import parse_bool, parse_int, parse_tuple

    op_name = node.op.name
    if op_name not in LAYER_INPUTS:
        return
    roles = LAYER_INPUTS[op_name](node.attrs)
    data_spec = out_specs.get((id(node.inputs[0][0]), node.inputs[0][1]))
    if data_spec is None:
        return
    dshape = data_spec.shape
    a = node.attrs

    # ops a parameter may sit behind without changing shape (AMP casts,
    # INT8 fake-quant chains, stop-gradient)
    _SHAPE_PRESERVING = {"_contrib_quantize", "_contrib_quantize_v2",
                         "_contrib_dequantize", "amp_cast", "Cast", "cast",
                         "_copy", "identity", "BlockGrad", "stop_gradient"}

    def fill(pos, shape):
        if pos >= len(node.inputs):
            return
        p, i = node.inputs[pos]
        while p.op is not None and p.op.name in _SHAPE_PRESERVING and i == 0:
            p, i = p.inputs[0]
        if p.op is None and out_specs.get((id(p), i)) is None:
            out_specs[(id(p), i)] = var_spec(p.name, shape)

    if op_name == "FullyConnected":
        nh = parse_int(a.get("num_hidden"))
        flatten = parse_bool(a.get("flatten", True), True)
        in_feat = int(_np.prod(dshape[1:])) if flatten else int(dshape[-1])
        fill(roles.index("weight"), (nh, in_feat))
        if "bias" in roles:
            fill(roles.index("bias"), (nh,))
    elif op_name in ("Convolution", "Deconvolution"):
        kernel = parse_tuple(a.get("kernel"))
        nf = parse_int(a.get("num_filter"))
        ng = parse_int(a.get("num_group", 1), 1)
        cin = int(dshape[1])
        if op_name == "Convolution":
            wshape = (nf, cin // ng) + tuple(kernel)
        else:  # Deconvolution stores (in_c, nf/g, *kernel)
            wshape = (cin, nf // ng) + tuple(kernel)
        fill(roles.index("weight"), wshape)
        if "bias" in roles:
            fill(roles.index("bias"), (nf,))
    elif op_name in ("BatchNorm", "_contrib_SyncBatchNorm"):
        axis = parse_int(a.get("axis", 1), 1)
        c = int(dshape[axis])
        for r in ("gamma", "beta", "moving_mean", "moving_var"):
            fill(roles.index(r), (c,))
    elif op_name in ("LayerNorm", "InstanceNorm"):
        axis = parse_int(a.get("axis", -1 if op_name == "LayerNorm" else 1),
                         -1 if op_name == "LayerNorm" else 1)
        c = int(dshape[axis])
        fill(roles.index("gamma"), (c,))
        fill(roles.index("beta"), (c,))
    elif op_name == "Embedding":
        fill(roles.index("weight"), (parse_int(a.get("input_dim")),
                                     parse_int(a.get("output_dim"))))
    elif op_name == "LeakyReLU" and "gamma" in roles:
        fill(roles.index("gamma"), (int(dshape[1]),))
    elif op_name in ("SoftmaxOutput", "SVMOutput"):
        multi = parse_bool(node.attrs.get("multi_output", False))
        fill(roles.index("label"),
             (int(dshape[0]),) + ((tuple(dshape[2:])) if multi else ()))
    elif op_name in ("LinearRegressionOutput", "LogisticRegressionOutput",
                     "MAERegressionOutput"):
        fill(roles.index("label"), tuple(int(x) for x in dshape))
    elif op_name == "RNN":
        # flat cuDNN-canonical parameter vector (see ops/nn.py rnn):
        # per layer/dir W(G·H×in) + R(G·H×H), then biases 2·G·H each
        h = parse_int(a.get("state_size"))
        layers = parse_int(a.get("num_layers", 1), 1)
        d = 2 if parse_bool(a.get("bidirectional", False)) else 1
        g = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4,
             "gru": 3}[str(a.get("mode", "lstm"))]
        cin = int(dshape[2])
        total = 0
        for layer in range(layers):
            in_sz = cin if layer == 0 else h * d
            total += d * (g * h * in_sz + g * h * h + 2 * g * h)
        fill(1, (total,))


def _input_order(op, named_inputs):
    if op.name in LAYER_INPUTS:
        # build a dummy attrs view: caller attrs already merged
        return LAYER_INPUTS[op.name]({})
    # generic: alphabetical? use common conventions
    common = ["data", "lhs", "rhs", "label", "weight", "bias", "index",
              "indices", "condition", "x", "y", "a", "b"]
    keys = list(named_inputs.keys())
    return sorted(keys, key=lambda k: common.index(k) if k in common else 99)


# --------------------------------------------------------------------------
# Fluent tensor methods (reference symbol.py generates these from the op
# registry — the curated inventory below mirrors its FLUENT list)
_FLUENT_METHODS = (
    "max", "min", "prod", "argmax", "argmin", "argsort", "sort", "topk",
    "sqrt", "rsqrt", "cbrt", "log", "log2", "log10", "log1p", "exp",
    "expm1", "square", "abs", "sign", "round", "rint", "floor", "ceil",
    "trunc", "sigmoid", "tanh", "relu", "softmax", "log_softmax", "erf",
    "flatten", "norm", "nansum", "nanprod", "clip", "expand_dims",
    "squeeze", "split", "slice_axis", "slice_like", "take", "one_hot",
    "tile", "repeat", "pad", "flip", "reshape_like", "broadcast_to",
    "broadcast_like", "swapaxes", "diag", "sin", "cos", "tan", "arcsin",
    "arccos", "arctan", "sinh", "cosh", "arctanh", "degrees", "radians",
    "gamma", "gammaln",
)


def _install_fluent_methods():
    for _name in _FLUENT_METHODS:
        if hasattr(Symbol, _name):
            continue
        _op = _reg.get(_name)
        if _op is None:
            continue

        # make_sym_func's fn takes the data symbol first — it IS the
        # bound method
        setattr(Symbol, _name, make_sym_func(_op))


_install_fluent_methods()


def _symbol_call(self, *args, name=None, **kwargs):
    """Compose: re-bind this symbol's variable inputs to other symbols
    (reference ``symbol.cc Compose`` / ``Symbol.__call__``).  Positional
    arguments map onto free variables in ``list_arguments`` order that are
    not already bound by keyword."""
    repl = {}
    for k, v in kwargs.items():
        if not isinstance(v, Symbol):
            raise TypeError(f"compose expects Symbol for {k!r}")
        repl[k] = v
    if args:
        free = [n for n in self.list_arguments() if n not in repl]
        if len(args) > len(free):
            raise ValueError("too many positional compose arguments")
        for a, n in zip(args, free):
            if not isinstance(a, Symbol):
                raise TypeError("compose expects Symbol arguments")
            repl[n] = a
    unknown = set(repl) - set(self.list_arguments()) \
        - set(self.list_auxiliary_states())
    if unknown:
        raise ValueError(f"compose: no variable named {sorted(unknown)}")
    for k, v in repl.items():
        if len(v._outputs) != 1:
            raise ValueError(
                f"compose: {k!r} is bound to a grouped symbol with "
                f"{len(v._outputs)} outputs — composition only supports "
                f"single-output operands (reference symbol.cc Compose)")

    new_out = {}        # id(old node) -> list[(new node, out idx)]
    for node in self._topo():
        if node.op is None:
            if node.name in repl:
                new_out[id(node)] = list(repl[node.name]._outputs)
            else:
                v = _Node(None, node.name, [], {}, 1, dict(node.attr_dict))
                new_out[id(node)] = [(v, 0)]
            continue
        inputs = [new_out[id(p)][i] for (p, i) in node.inputs]
        nn = _Node(node.op, node.name, inputs, dict(node.attrs),
                   node.num_outputs, dict(node.attr_dict))
        nn.subgraphs = node.subgraphs
        new_out[id(node)] = [(nn, i) for i in range(node.num_outputs)]
    outs = []
    for (n, i) in self._outputs:
        outs.append(new_out[id(n)][i])
    result = Symbol(outs)
    if name is not None and len(result._outputs) == 1 \
            and result._outputs[0][0].op is not None:
        result._outputs[0][0].name = name      # reference renames the head
    return result


Symbol.__call__ = _symbol_call
