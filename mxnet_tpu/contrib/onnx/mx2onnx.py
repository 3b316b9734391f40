"""Symbol → ONNX graph conversion (reference
``python/mxnet/contrib/onnx/mx2onnx/export_onnx.py`` MXNetGraph +
``_op_translations.py`` converter table).

The converter is wheel-independent: it produces a plain-dict ONNX graph
(nodes with ``op_type``/``inputs``/``outputs``/``attrs``, initializers as
numpy arrays) that round-trips through :mod:`.onnx2mx` and is structurally
testable without protobuf.  Only :func:`graph_to_proto` (and therefore
``export_model``'s file emission) needs the real ``onnx`` package.

Graph dict schema::

    {"nodes": [{"op_type", "name", "inputs": [names], "outputs": [names],
                "attrs": {...python values...}}, ...],
     "inputs": [{"name", "shape", "dtype"}],
     "outputs": [{"name"}],
     "initializers": {name: np.ndarray}}
"""
from __future__ import annotations

import ast
import json

import numpy as _np

_MX2ONNX = {}


def register(op_name):
    def deco(fn):
        _MX2ONNX[op_name] = fn
        return fn
    return deco


def _parse(v, default=None):
    """MXNet string attr → python value ('(2, 2)' → (2, 2), 'True' → True)."""
    if v is None:
        return default
    if not isinstance(v, str):
        return v
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def _tuple2(v, default):
    t = _parse(v, default)
    if isinstance(t, int):
        t = (t,) * len(default)
    return tuple(int(x) for x in t)


class _Ctx:
    """Conversion state handed to each op converter."""

    def __init__(self, params, input_map):
        self.params = params          # name -> np.ndarray (initializers)
        self.input_map = input_map    # mx node-name -> onnx tensor name
        self.nodes = []
        self.extra_initializers = {}

    def inp(self, name):
        return self.input_map.get(name, name)

    def add(self, op_type, name, inputs, attrs=None, outputs=None,
            domain=None):
        node = {"op_type": op_type, "name": name, "inputs": list(inputs),
                "outputs": list(outputs) if outputs else [name],
                "attrs": dict(attrs or {})}
        if domain:
            node["domain"] = domain
        self.nodes.append(node)
        return node["outputs"][0]


def _require_channel_first(name, attrs):
    """ONNX Conv/Pool semantics are channel-first; exporting an NHWC-built
    node as-is would silently emit wrong-axis kernel_shape/pads."""
    layout = attrs.get("layout")
    if layout in (None, "None", ""):        # default = channel-first
        return
    layout = str(layout)
    if layout[1] != "C":
        raise NotImplementedError(
            f"ONNX export of node {name!r} with channel-last layout "
            f"{layout!r} is not supported — rebuild the network with the "
            f"default channel-first layout (e.g. NCHW) before exporting")


# --------------------------------------------------------------- converters
@register("Convolution")
def _conv(ctx, name, ins, attrs):
    _require_channel_first(name, attrs)
    kernel = _tuple2(attrs.get("kernel"), (1, 1))
    a = {"kernel_shape": kernel,
         "strides": _tuple2(attrs.get("stride"), (1,) * len(kernel)),
         "dilations": _tuple2(attrs.get("dilate"), (1,) * len(kernel)),
         "group": int(_parse(attrs.get("num_group"), 1))}
    pad = _tuple2(attrs.get("pad"), (0,) * len(kernel))
    a["pads"] = pad + pad            # onnx wants begin+end per spatial axis
    return ctx.add("Conv", name, ins, a)


@register("Deconvolution")
def _deconv(ctx, name, ins, attrs):
    _require_channel_first(name, attrs)
    kernel = _tuple2(attrs.get("kernel"), (1, 1))
    pad = _tuple2(attrs.get("pad"), (0,) * len(kernel))
    a = {"kernel_shape": kernel,
         "strides": _tuple2(attrs.get("stride"), (1,) * len(kernel)),
         "dilations": _tuple2(attrs.get("dilate"), (1,) * len(kernel)),
         "group": int(_parse(attrs.get("num_group"), 1)),
         "pads": pad + pad}
    return ctx.add("ConvTranspose", name, ins, a)


@register("BatchNorm")
def _batchnorm(ctx, name, ins, attrs):
    # ins = [data, gamma, beta, moving_mean, moving_var]
    if _parse(attrs.get("fix_gamma"), True) in (True, 1, "True"):
        gamma_name = ins[1]
        if gamma_name in ctx.params:
            ctx.extra_initializers[gamma_name] = _np.ones_like(
                ctx.params[gamma_name])
    return ctx.add("BatchNormalization", name, ins, {
        "epsilon": float(_parse(attrs.get("eps"), 1e-3)),
        "momentum": float(_parse(attrs.get("momentum"), 0.9))})


_ACT = {"relu": "Relu", "sigmoid": "Sigmoid", "tanh": "Tanh",
        "softrelu": "Softplus", "softsign": "Softsign"}


@register("Activation")
def _activation(ctx, name, ins, attrs):
    return ctx.add(_ACT[attrs.get("act_type", "relu")], name, ins)


@register("LeakyReLU")
def _leaky(ctx, name, ins, attrs):
    act = attrs.get("act_type", "leaky")
    if act == "leaky":
        return ctx.add("LeakyRelu", name, ins[:1],
                       {"alpha": float(_parse(attrs.get("slope"), 0.25))})
    if act == "elu":
        return ctx.add("Elu", name, ins[:1],
                       {"alpha": float(_parse(attrs.get("slope"), 0.25))})
    if act == "prelu":
        return ctx.add("PRelu", name, ins)
    if act == "gelu":
        # 0.5 * x * (1 + erf(x / sqrt 2)), as ops/elemwise.py::gelu
        def const(tag, value):
            ctx.extra_initializers[f"{name}_{tag}"] = _np.asarray(
                value, dtype=_np.float32)
            return f"{name}_{tag}"
        x = ins[0]
        e = ctx.add("Erf", name + "_erf", [ctx.add(
            "Div", name + "_scaled", [x, const("sqrt2", 2.0 ** 0.5)])])
        half = ctx.add("Mul", name + "_half", [x, const("half", 0.5)])
        return ctx.add("Mul", name, [half, ctx.add(
            "Add", name + "_cdf2", [e, const("one", 1.0)])])
    raise NotImplementedError(f"LeakyReLU act_type={act}")


@register("Pooling")
def _pooling(ctx, name, ins, attrs):
    _require_channel_first(name, attrs)
    ptype = attrs.get("pool_type", "max")
    if _parse(attrs.get("global_pool"), False) in (True, 1, "True"):
        op = {"max": "GlobalMaxPool", "avg": "GlobalAveragePool"}[ptype]
        return ctx.add(op, name, ins)
    kernel = _tuple2(attrs.get("kernel"), (1, 1))
    pad = _tuple2(attrs.get("pad"), (0,) * len(kernel))
    a = {"kernel_shape": kernel,
         "strides": _tuple2(attrs.get("stride"), (1,) * len(kernel)),
         "pads": pad + pad}
    if str(attrs.get("pooling_convention", "valid")) == "full":
        a["ceil_mode"] = 1           # ONNX MaxPool/AveragePool opset>=10
    if ptype == "avg":
        a["count_include_pad"] = 0 \
            if attrs.get("count_include_pad", "True") in ("False", False) \
            else 1
        return ctx.add("AveragePool", name, ins, a)
    return ctx.add("MaxPool", name, ins, a)


@register("FullyConnected")
def _fc(ctx, name, ins, attrs):
    if _parse(attrs.get("flatten"), True) in (False, 0, "False"):
        # flatten=False: y = x @ W.T (+ b) over the last axis, batched —
        # Gemm is 2-D-only, so emit Transpose(W) + MatMul (+ Add)
        wt = ctx.add("Transpose", name + "_wT", [ins[1]], {"perm": (1, 0)})
        no_bias = _parse(attrs.get("no_bias"), False) in (True, 1, "True")
        if no_bias:
            return ctx.add("MatMul", name, [ins[0], wt])
        mm = ctx.add("MatMul", name + "_mm", [ins[0], wt])
        return ctx.add("Add", name, [mm, ins[2]])
    flat = ctx.add("Flatten", name + "_flatten", ins[:1], {"axis": 1})
    no_bias = _parse(attrs.get("no_bias"), False) in (True, 1, "True")
    if no_bias:
        # Gemm needs C; synthesize a zero bias initializer
        w = ctx.params.get(ins[1])
        zname = name + "_zero_bias"
        ctx.extra_initializers[zname] = _np.zeros(
            (int(_parse(attrs.get("num_hidden"),
                        w.shape[0] if w is not None else 0)),), "float32")
        gemm_in = [flat, ins[1], zname]
    else:
        gemm_in = [flat, ins[1], ins[2]]
    return ctx.add("Gemm", name, gemm_in,
                   {"alpha": 1.0, "beta": 1.0, "transA": 0, "transB": 1})


@register("Flatten")
def _flatten(ctx, name, ins, attrs):
    return ctx.add("Flatten", name, ins, {"axis": 1})


@register("SoftmaxOutput")
def _softmax_output(ctx, name, ins, attrs):
    # label input is dropped; inference softmax over axis 1 (reference
    # _op_translations softmax_output)
    return ctx.add("Softmax", name, ins[:1], {"axis": 1})


@register("softmax")
def _softmax(ctx, name, ins, attrs):
    return ctx.add("Softmax", name, ins,
                   {"axis": int(_parse(attrs.get("axis"), -1))})


@register("Concat")
def _concat(ctx, name, ins, attrs):
    return ctx.add("Concat", name, ins,
                   {"axis": int(_parse(attrs.get("dim"), 1))})


@register("Dropout")
def _dropout(ctx, name, ins, attrs):
    return ctx.add("Dropout", name, ins,
                   {"ratio": float(_parse(attrs.get("p"), 0.5))})





@register("transpose")
def _transpose(ctx, name, ins, attrs):
    axes = _parse(attrs.get("axes"), None)
    a = {"perm": tuple(int(x) for x in axes)} if axes else {}
    return ctx.add("Transpose", name, ins, a)


@register("Embedding")
def _embedding(ctx, name, ins, attrs):
    # ONNX Gather(data=weight, indices)
    return ctx.add("Gather", name, [ins[1], ins[0]], {"axis": 0})


@register("mean")
def _mean(ctx, name, ins, attrs):
    axis = _parse(attrs.get("axis"), None)
    a = {"keepdims": 1 if _parse(attrs.get("keepdims"), False)
         in (True, 1, "True") else 0}
    if axis is not None:
        a["axes"] = tuple(axis) if isinstance(axis, (tuple, list)) \
            else (int(axis),)
    return ctx.add("ReduceMean", name, ins, a)


@register("clip")
def _clip(ctx, name, ins, attrs):
    # opset>=11 Clip: min/max are INPUTS (the attr form is only legal <=6)
    mn, mx = name + "_min", name + "_max"
    ctx.extra_initializers[mn] = _np.asarray(
        float(_parse(attrs.get("a_min"), 0.0)), dtype=_np.float32)
    ctx.extra_initializers[mx] = _np.asarray(
        float(_parse(attrs.get("a_max"), 0.0)), dtype=_np.float32)
    return ctx.add("Clip", name, [ins[0], mn, mx])


def _binop(onnx_op):
    def cv(ctx, name, ins, attrs):
        return ctx.add(onnx_op, name, ins)
    return cv


for _mx, _ox in [("elemwise_add", "Add"), ("broadcast_add", "Add"),
                 ("_plus", "Add"),
                 ("elemwise_sub", "Sub"), ("broadcast_sub", "Sub"),
                 ("elemwise_mul", "Mul"), ("broadcast_mul", "Mul"),
                 ("elemwise_div", "Div"), ("broadcast_div", "Div")]:
    register(_mx)(_binop(_ox))


@register("dot")
def _dot(ctx, name, ins, attrs):
    # NOTE: assumes matrix (2-D) semantics — mx N-D dot is tensordot(axes=1)
    # with full-reverse transposes, which MatMul does not express; an N-D
    # transpose import fails loudly on the 2-D perm rather than silently
    a, b = ins
    if _parse(attrs.get("transpose_a"), False) in (True, 1, "True"):
        a = ctx.add("Transpose", name + "_ta", [a], {"perm": (1, 0)})
    if _parse(attrs.get("transpose_b"), False) in (True, 1, "True"):
        b = ctx.add("Transpose", name + "_tb", [b], {"perm": (1, 0)})
    return ctx.add("MatMul", name, [a, b])


def _scalar_op(onnx_op):
    def cv(ctx, name, ins, attrs):
        sname = name + "_scalar"
        ctx.extra_initializers[sname] = _np.asarray(
            float(_parse(attrs.get("scalar"), 0.0)), dtype=_np.float32)
        return ctx.add(onnx_op, name, [ins[0], sname])
    return cv


for _mx, _ox in [("_plus_scalar", "Add"), ("_minus_scalar", "Sub"),
                 ("_mul_scalar", "Mul"), ("_div_scalar", "Div")]:
    register(_mx)(_scalar_op(_ox))


def _rscalar_op(onnx_op):
    def cv(ctx, name, ins, attrs):
        sname = name + "_scalar"
        ctx.extra_initializers[sname] = _np.asarray(
            float(_parse(attrs.get("scalar"), 0.0)), dtype=_np.float32)
        return ctx.add(onnx_op, name, [sname, ins[0]])
    return cv


for _mx, _ox in [("_rminus_scalar", "Sub"), ("_rdiv_scalar", "Div")]:
    register(_mx)(_rscalar_op(_ox))


for _mx, _ox in [("relu", "Relu"), ("sigmoid", "Sigmoid"), ("tanh", "Tanh"),
                 ("exp", "Exp"), ("log", "Log"), ("sqrt", "Sqrt"),
                 ("abs", "Abs"), ("negative", "Neg"), ("identity", "Identity"),
                 ("BlockGrad", "Identity")]:
    register(_mx)(_binop(_ox))


# ------------------------------------------------------------------ exporter
def export_graph(sym, params, input_shapes, input_dtype="float32"):
    """Convert a Symbol + params to the plain-dict ONNX graph.

    ``params``: dict name → NDArray/np.ndarray (arg + aux, as saved by
    ``save_checkpoint``; ``arg:``/``aux:`` prefixes accepted).
    ``input_shapes``: dict data-name → shape (or a single shape for the
    sole non-param input).
    """
    graph = json.loads(sym.tojson())
    nodes, heads = graph["nodes"], graph["heads"]
    np_params = {}
    for k, v in (params or {}).items():
        k = k.split(":", 1)[1] if k.startswith(("arg:", "aux:")) else k
        np_params[k] = v.asnumpy() if hasattr(v, "asnumpy") else _np.asarray(v)

    # one output tensor name per (node, out_idx).  MXNet JSON wires inputs
    # by index, so duplicate node names are legal there (Gluon-traced
    # graphs name every op "fwd") — ONNX wires by NAME, so duplicates must
    # be uniquified here
    taken = set()
    uniq = []
    for n in nodes:
        name = n["name"]
        if n["op"] == "null":
            # duplicate variable names intentionally alias one tensor
            uniq.append(name)
            taken.add(name)
            continue
        cand, k = name, 0
        while cand in taken:
            k += 1
            cand = f"{name}_n{k}"
        uniq.append(cand)
        taken.add(cand)

    def out_name(i, j):
        base = uniq[i]
        return base if j == 0 else f"{base}_out{j}"

    ctx = _Ctx(np_params, {})
    for i, n in enumerate(nodes):
        if n["op"] == "null":
            continue
        conv = _MX2ONNX.get(n["op"])
        if conv is None:
            raise NotImplementedError(
                f"no ONNX converter for op {n['op']!r} (node {n['name']})")
        ins = [out_name(src, j) for (src, j, _) in n["inputs"]]
        out = conv(ctx, uniq[i], ins, n.get("attrs", {}))
        # every converter's final node must carry the mx node's name — that
        # is how downstream nodes reference this output
        assert out == out_name(i, 0), \
            f"converter for {n['op']} renamed output {out!r}"

    # graph inputs = variables the emitted nodes actually reference (labels
    # consumed only by dropped training heads vanish, like the reference
    # exporter's forbidden/label handling)
    used = {x for n in ctx.nodes for x in n["inputs"]}
    data_inputs = [n["name"] for n in nodes
                   if n["op"] == "null" and n["name"] not in np_params
                   and n["name"] in used]
    if not isinstance(input_shapes, dict):
        assert len(data_inputs) == 1, \
            f"need an input_shapes dict for inputs {data_inputs}"
        input_shapes = {data_inputs[0]: tuple(input_shapes)}

    inits = dict(np_params)
    inits.update(ctx.extra_initializers)
    inits = {k: v for k, v in inits.items() if k in used}
    return {
        "nodes": ctx.nodes,
        "inputs": [{"name": d, "shape": tuple(input_shapes[d]),
                    "dtype": input_dtype} for d in data_inputs],
        "outputs": [{"name": out_name(i, j)} for (i, j, _) in heads],
        "initializers": inits,
    }


# all emitted ops use their opset-17 forms: Slice (input-form since 10),
# Clip (11), Pad (11), Unsqueeze/Split (13), LayerNormalization (17)
OPSET = 17


def graph_to_proto(graph):
    """Plain-dict graph → onnx.ModelProto (wheel path; the wheel-free
    serializer is :func:`graph_to_bytes`)."""
    from . import _require_onnx
    _require_onnx()
    import onnx
    from onnx import helper, numpy_helper, TensorProto

    from .protobuf import DTYPE_TO_ONNX as dt   # one shared dtype table
    onodes = []
    for n in graph["nodes"]:
        attrs = {}
        for k, v in n["attrs"].items():
            attrs[k] = list(v) if isinstance(v, tuple) else v
        if n["op_type"] == "Cast":
            # the dict carries dtype names; the proto wants the enum
            attrs["to"] = dt[str(attrs.get("to", "float32"))]
        onodes.append(helper.make_node(n["op_type"], n["inputs"],
                                       n["outputs"], name=n["name"],
                                       domain=n.get("domain", ""), **attrs))
    inputs = [helper.make_tensor_value_info(i["name"], dt[i["dtype"]],
                                            list(i["shape"]))
              for i in graph["inputs"]]
    outputs = [helper.make_tensor_value_info(o["name"], dt["float32"], None)
               for o in graph["outputs"]]
    inits = [numpy_helper.from_array(v, name=k)
             for k, v in graph["initializers"].items()]
    g = helper.make_graph(onodes, "mxnet_tpu", inputs, outputs,
                          initializer=inits)
    # opset 17: Slice/Clip/Unsqueeze are emitted in input form (legal
    # since 10/11/13) and LayerNormalization is a default-domain op (17)
    return helper.make_model(g, opset_imports=[
        helper.make_opsetid("", OPSET), helper.make_opsetid("mxnet", 1)])


def graph_to_bytes(graph):
    """Plain-dict graph → real ONNX ModelProto bytes via the hand-written
    wire-format serializer (:mod:`.protobuf`) — no wheel needed.  The
    bytes parse back through ``protobuf.bytes_to_model``, through
    ``protoc --decode_raw``, and through the onnx wheel where present."""
    from .protobuf import model_to_bytes
    import copy

    g = {"nodes": [], "inputs": graph["inputs"],
         "outputs": graph["outputs"],
         "initializers": graph["initializers"]}
    for n in graph["nodes"]:
        n = copy.copy(n)
        if n["op_type"] == "Cast":
            # the dict carries numpy dtype names; the proto wants the enum
            from .protobuf import DTYPE_TO_ONNX
            attrs = dict(n["attrs"])
            attrs["to"] = DTYPE_TO_ONNX[str(attrs.get("to", "float32"))]
            n["attrs"] = attrs
        g["nodes"].append(n)
    return model_to_bytes(g, opset=OPSET)


def export_model(sym, params, input_shape, input_type="float32",
                 onnx_file_path="model.onnx", verbose=False):
    """Reference ``mx2onnx/export_model.py:export_model``: converts and
    writes a real ``.onnx`` protobuf file (wheel-free — see
    :func:`graph_to_bytes`)."""
    graph = export_graph(sym, params, input_shape, input_dtype=input_type)
    with open(onnx_file_path, "wb") as f:
        f.write(graph_to_bytes(graph))
    if verbose:
        print(f"exported {onnx_file_path}")
    return onnx_file_path


# ------------------------------------------------- transformer-family ops
@register("LayerNorm")
def _layernorm(ctx, name, ins, attrs):
    return ctx.add("LayerNormalization", name, ins, {
        "axis": int(_parse(attrs.get("axis"), -1)),
        "epsilon": float(_parse(attrs.get("eps"), 1e-5))})


@register("erf")
def _erf(ctx, name, ins, attrs):
    return ctx.add("Erf", name, ins)


@register("_copy")
def _copy_cv(ctx, name, ins, attrs):
    return ctx.add("Identity", name, ins)


@register("cast")
def _cast_cv(ctx, name, ins, attrs):
    return ctx.add("Cast", name, ins,
                   {"to": str(_parse(attrs.get("dtype"), "float32"))})


@register("expand_dims")
def _expand_dims(ctx, name, ins, attrs):
    # opset>=13 Unsqueeze: axes is an INPUT tensor, not an attribute
    aname = _int64_init(ctx, name + "_axes",
                        [int(_parse(attrs.get("axis"), 0))])
    return ctx.add("Unsqueeze", name, [ins[0], aname])


@register("reshape")
def _reshape(ctx, name, ins, attrs):
    # mx reshape 0/-1 specials share ONNX Reshape semantics (allowzero=0);
    # the MXNet-only -2/-3/-4 specials are NOT ONNX — emit those under the
    # mxnet domain so a foreign runtime fails loudly instead of silently
    # misreshaping (the dict round-trip maps them back to mx reshape)
    shape = tuple(int(x) for x in _parse(attrs.get("shape"), ()))
    sname = name + "_shape"
    ctx.extra_initializers[sname] = _np.asarray(shape, dtype=_np.int64)
    domain = "mxnet" if any(x < -1 for x in shape) else None
    return ctx.add("Reshape", name, [ins[0], sname], domain=domain)


@register("slice_axis")
def _slice_axis(ctx, name, ins, attrs):
    # opset>=10 Slice: starts/ends/axes are INPUTS (attr form legal <=9)
    ax = int(_parse(attrs.get("axis"), 0))
    begin = int(_parse(attrs.get("begin"), 0))
    end = _parse(attrs.get("end"), None)
    names = [_int64_init(ctx, name + suffix, [int(val)])
             for suffix, val in (("_starts", begin),
                                 ("_ends", int(end) if end is not None
                                  else 2**31 - 1),
                                 ("_axes", ax))]
    return ctx.add("Slice", name, [ins[0]] + names)


@register("slice_like")
def _slice_like(ctx, name, ins, attrs):
    # no ONNX builtin: emitted under the custom mxnet domain (the dict
    # round-trip and graph_to_proto keep it; foreign runtimes would need
    # the Shape→Gather→Slice expansion)
    axes = _parse(attrs.get("axes"), None)
    a = {"axes": tuple(int(x) for x in axes) if axes else ()}
    return ctx.add("SliceLike", name, ins, a, domain="mxnet")


@register("split")
def _split(ctx, name, ins, attrs):
    n = int(_parse(attrs.get("num_outputs"), 1))
    ax = int(_parse(attrs.get("axis"), 1))
    outs = [name] + [f"{name}_out{j}" for j in range(1, n)]
    if _parse(attrs.get("squeeze_axis"), False) in (True, 1, "True"):
        # SliceChannel(squeeze_axis=True): Split keeps the split axis, so
        # each output gets a Squeeze(axes=[ax]) (input form, opset 13)
        pres = [f"{name}_pre{j}" for j in range(n)]
        ctx.add("Split", name + "_split", ins, {"axis": ax}, outputs=pres)
        aname = _int64_init(ctx, name + "_sq_axes", [ax])
        for j in range(n):
            ctx.add("Squeeze", outs[j], [pres[j], aname],
                    outputs=[outs[j]])
        return outs[0]
    ctx.add("Split", name, ins, {"axis": ax}, outputs=outs)
    return outs[0]


@register("_arange")
def _arange_cv(ctx, name, ins, attrs):
    # static attrs: constant-fold to an initializer + Identity
    start = float(_parse(attrs.get("start"), 0.0))
    stop = _parse(attrs.get("stop"), None)
    step = float(_parse(attrs.get("step"), 1.0))
    dt = str(_parse(attrs.get("dtype"), "float32"))
    arr = _np.arange(start, float(stop) if stop is not None else None,
                     step).astype(dt if dt != "bfloat16" else "float32")
    rep = int(_parse(attrs.get("repeat"), 1))
    if rep > 1:
        arr = _np.repeat(arr, rep)
    cname = name + "_const"
    ctx.extra_initializers[cname] = arr
    return ctx.add("Identity", name, [cname])


@register("_batched_gather")
def _batched_gather_cv(ctx, name, ins, attrs):
    # (B,T,C) @ (B,M) → GatherND(batch_dims=1) over (B,M,1) int64 indices
    c = ctx.add("Cast", name + "_idx64", [ins[1]], {"to": "int64"})
    u = ctx.add("Unsqueeze", name + "_idx3", [c], {"axes": (2,)})
    return ctx.add("GatherND", name, [ins[0], u], {"batch_dims": 1})


@register("batch_dot")
def _batch_dot(ctx, name, ins, attrs):
    a, b = ins
    if _parse(attrs.get("transpose_a"), False) in (True, 1, "True"):
        a = ctx.add("Transpose", name + "_ta", [a], {"perm": (0, 2, 1)})
    if _parse(attrs.get("transpose_b"), False) in (True, 1, "True"):
        b = ctx.add("Transpose", name + "_tb", [b], {"perm": (0, 2, 1)})
    return ctx.add("MatMul", name, [a, b])


# ---------------------------------------------------- breadth tranche (r3)
# Reference table: mx2onnx/_op_translations.py (98 @mx_op.register entries).
# Everything below emits opset-17-legal forms (axes/shape/repeats as inputs
# where the opset moved them there).
def _int64_init(ctx, name, values):
    ctx.extra_initializers[name] = _np.asarray(values, dtype=_np.int64)
    return name


for _mx, _ox in [("reciprocal", "Reciprocal"), ("ceil", "Ceil"),
                 ("floor", "Floor"), ("sin", "Sin"), ("cos", "Cos"),
                 ("tan", "Tan"), ("arcsin", "Asin"), ("arccos", "Acos"),
                 ("arctan", "Atan"), ("sinh", "Sinh"), ("cosh", "Cosh"),
                 ("tanh", "Tanh"), ("round", "Round"), ("sign", "Sign"),
                 ("softsign", "Softsign"),
                 ("_maximum", "Max"), ("_minimum", "Min"),
                 ("broadcast_maximum", "Max"), ("broadcast_minimum", "Min"),
                 ("broadcast_power", "Pow"), ("_power", "Pow"),
                 ("add_n", "Sum"), ("ElementWiseSum", "Sum"),
                 ("shape_array", "Shape"), ("size_array", "Size")]:
    register(_mx)(_binop(_ox))


def _not_equal_cv(ctx, name, ins, attrs):
    # no ONNX NotEqual op: Equal → Not, with the bool↔float casts the mx
    # dtype contract needs
    eq = ctx.add("Equal", name + "_eq", ins)
    ne = ctx.add("Not", name + "_not", [eq])
    return ctx.add("Cast", name, [ne], {"to": "float32"})


register("broadcast_not_equal")(_not_equal_cv)
register("_not_equal")(_not_equal_cv)

register("_power_scalar")(_scalar_op("Pow"))
register("_maximum_scalar")(_scalar_op("Max"))
register("_minimum_scalar")(_scalar_op("Min"))


@register("square")
def _square(ctx, name, ins, attrs):
    # no ONNX Square: x*x keeps it a single fused Mul everywhere
    return ctx.add("Mul", name, [ins[0], ins[0]])


@register("logical_not")
def _logical_not(ctx, name, ins, attrs):
    b = ctx.add("Cast", name + "_b", ins, {"to": "bool"})
    n = ctx.add("Not", name + "_not", [b])
    return ctx.add("Cast", name, [n], {"to": "float32"})


def _cmp_op(onnx_op):
    # mx comparisons return float 0/1; ONNX comparators return bool
    def cv(ctx, name, ins, attrs):
        c = ctx.add(onnx_op, name + "_cmp", ins)
        return ctx.add("Cast", name, [c], {"to": "float32"})
    return cv


for _mx, _ox in [("broadcast_equal", "Equal"),
                 ("broadcast_greater", "Greater"),
                 ("broadcast_lesser", "Less"),
                 ("broadcast_greater_equal", "GreaterOrEqual"),
                 ("broadcast_lesser_equal", "LessOrEqual")]:
    register(_mx)(_cmp_op(_ox))


def _logical_op(onnx_op):
    def cv(ctx, name, ins, attrs):
        bs = [ctx.add("Cast", f"{name}_b{i}", [x], {"to": "bool"})
              for i, x in enumerate(ins)]
        o = ctx.add(onnx_op, name + "_op", bs)
        return ctx.add("Cast", name, [o], {"to": "float32"})
    return cv


for _mx, _ox in [("broadcast_logical_and", "And"),
                 ("broadcast_logical_or", "Or"),
                 ("broadcast_logical_xor", "Xor")]:
    register(_mx)(_logical_op(_ox))


def _reduce_op(onnx_op, axes_as_input=False):
    def cv(ctx, name, ins, attrs):
        axes = _parse(attrs.get("axis"), None)
        if axes is not None and not isinstance(axes, (tuple, list)):
            axes = (axes,)
        a = {"keepdims": 1 if _parse(attrs.get("keepdims"), False)
             in (True, 1, "True") else 0}
        if axes_as_input:
            # ReduceSum moved axes to an input at opset 13
            extra = [_int64_init(ctx, name + "_axes",
                                 [int(x) for x in axes])] if axes else []
            return ctx.add(onnx_op, name, [ins[0]] + extra, a)
        if axes:
            a["axes"] = tuple(int(x) for x in axes)
        return ctx.add(onnx_op, name, ins, a)
    return cv


register("sum")(_reduce_op("ReduceSum", axes_as_input=True))
register("max")(_reduce_op("ReduceMax"))
register("min")(_reduce_op("ReduceMin"))
register("prod")(_reduce_op("ReduceProd"))


@register("norm")
def _norm(ctx, name, ins, attrs):
    ordv = int(_parse(attrs.get("ord"), 2))
    axes = _parse(attrs.get("axis"), None)
    if axes is not None and not isinstance(axes, (tuple, list)):
        axes = (axes,)
    a = {"keepdims": 1 if _parse(attrs.get("keepdims"), False)
         in (True, 1, "True") else 0}
    if axes:
        a["axes"] = tuple(int(x) for x in axes)
    return ctx.add({1: "ReduceL1", 2: "ReduceL2"}[ordv], name, ins, a)


def _arg_op(onnx_op):
    def cv(ctx, name, ins, attrs):
        ax = _parse(attrs.get("axis"), None)
        a = {"axis": int(ax) if ax is not None else 0,
             "keepdims": 1 if _parse(attrs.get("keepdims"), False)
             in (True, 1, "True") else 0}
        o = ctx.add(onnx_op, name + "_i64", ins, a)
        # mx argmax/argmin return float32 — keep that dtype contract
        return ctx.add("Cast", name, [o], {"to": "float32"})
    return cv


register("argmax")(_arg_op("ArgMax"))
register("argmin")(_arg_op("ArgMin"))


@register("log_softmax")
def _log_softmax(ctx, name, ins, attrs):
    return ctx.add("LogSoftmax", name, ins,
                   {"axis": int(_parse(attrs.get("axis"), -1))})


@register("hard_sigmoid")
def _hard_sigmoid(ctx, name, ins, attrs):
    return ctx.add("HardSigmoid", name, ins,
                   {"alpha": float(_parse(attrs.get("alpha"), 0.2)),
                    "beta": float(_parse(attrs.get("beta"), 0.5))})


@register("squeeze")
def _squeeze_cv(ctx, name, ins, attrs):
    axes = _parse(attrs.get("axis"), None)
    if axes is None:
        return ctx.add("Squeeze", name, ins)
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    aname = _int64_init(ctx, name + "_axes", [int(x) for x in axes])
    return ctx.add("Squeeze", name, [ins[0], aname])


@register("broadcast_to")
def _broadcast_to(ctx, name, ins, attrs):
    shape = tuple(int(x) for x in _parse(attrs.get("shape"), ()))
    sname = _int64_init(ctx, name + "_shape", shape)
    return ctx.add("Expand", name, [ins[0], sname])


@register("tile")
def _tile(ctx, name, ins, attrs):
    reps = tuple(int(x) for x in _parse(attrs.get("reps"), ()))
    rname = _int64_init(ctx, name + "_reps", reps)
    return ctx.add("Tile", name, [ins[0], rname])


@register("depth_to_space")
def _d2s(ctx, name, ins, attrs):
    return ctx.add("DepthToSpace", name, ins,
                   {"blocksize": int(_parse(attrs.get("block_size"), 1)),
                    "mode": "DCR"})


@register("space_to_depth")
def _s2d(ctx, name, ins, attrs):
    return ctx.add("SpaceToDepth", name, ins,
                   {"blocksize": int(_parse(attrs.get("block_size"), 1))})


@register("Pad")
def _pad_cv(ctx, name, ins, attrs):
    # mx pad_width pairs (b0,e0,b1,e1,…) → ONNX [b…, e…]; input form (11+)
    pw = tuple(int(x) for x in _parse(attrs.get("pad_width"), ()))
    begins, ends = pw[0::2], pw[1::2]
    mode = str(_parse(attrs.get("mode"), "constant"))
    pname = _int64_init(ctx, name + "_pads", list(begins) + list(ends))
    inputs = [ins[0], pname]
    if mode == "constant":
        vname = name + "_value"
        ctx.extra_initializers[vname] = _np.asarray(
            float(_parse(attrs.get("constant_value"), 0.0)),
            dtype=_np.float32)
        inputs.append(vname)
    return ctx.add("Pad", name, inputs, {"mode": mode})


register("pad")(_pad_cv)


@register("LRN")
def _lrn(ctx, name, ins, attrs):
    return ctx.add("LRN", name, ins, {
        "alpha": float(_parse(attrs.get("alpha"), 1e-4)),
        "beta": float(_parse(attrs.get("beta"), 0.75)),
        "bias": float(_parse(attrs.get("knorm"), 2.0)),
        "size": int(_parse(attrs.get("nsize"), 5))})


@register("InstanceNorm")
def _instance_norm(ctx, name, ins, attrs):
    return ctx.add("InstanceNormalization", name, ins,
                   {"epsilon": float(_parse(attrs.get("eps"), 1e-3))})


@register("L2Normalization")
def _l2norm(ctx, name, ins, attrs):
    mode = str(_parse(attrs.get("mode"), "instance"))
    if mode != "channel":
        raise NotImplementedError(
            f"L2Normalization mode={mode!r}: only 'channel' maps to "
            "LpNormalization (reference _op_translations.py raises the "
            "same way)")
    return ctx.add("LpNormalization", name, ins, {"axis": 1, "p": 2})


@register("ROIPooling")
def _roipool(ctx, name, ins, attrs):
    hw = _tuple2(_parse(attrs.get("pooled_size"), (1, 1)), (1, 1))
    return ctx.add("MaxRoiPool", name, ins, {
        "pooled_shape": tuple(int(x) for x in hw),
        "spatial_scale": float(_parse(attrs.get("spatial_scale"), 1.0))})


@register("LogisticRegressionOutput")
def _logistic_out(ctx, name, ins, attrs):
    return ctx.add("Sigmoid", name, ins[:1])


@register("MakeLoss")
def _make_loss(ctx, name, ins, attrs):
    return ctx.add("Identity", name, ins[:1])


@register("_random_uniform")
def _random_uniform_cv(ctx, name, ins, attrs):
    return ctx.add("RandomUniform", name, [], {
        "low": float(_parse(attrs.get("low"), 0.0)),
        "high": float(_parse(attrs.get("high"), 1.0)),
        "shape": tuple(int(x) for x in _parse(attrs.get("shape"), ()))})


@register("_random_normal")
def _random_normal_cv(ctx, name, ins, attrs):
    return ctx.add("RandomNormal", name, [], {
        "mean": float(_parse(attrs.get("loc"), 0.0)),
        "scale": float(_parse(attrs.get("scale"), 1.0)),
        "shape": tuple(int(x) for x in _parse(attrs.get("shape"), ()))})


@register("_sample_multinomial")
def _sample_multinomial_cv(ctx, name, ins, attrs):
    shape = _parse(attrs.get("shape"), 1)
    n = int(shape[0]) if isinstance(shape, (tuple, list)) else int(shape)
    lg = ctx.add("Log", name + "_log", ins)   # mx takes probs, ONNX logits
    return ctx.add("Multinomial", name, [lg], {"sample_size": n})


@register("_linalg_gemm2")
def _linalg_gemm2_cv(ctx, name, ins, attrs):
    a, b = ins
    if _parse(attrs.get("transpose_a"), False) in (True, 1, "True"):
        a = ctx.add("Transpose", name + "_ta", [a], {"perm": (1, 0)})
    if _parse(attrs.get("transpose_b"), False) in (True, 1, "True"):
        b = ctx.add("Transpose", name + "_tb", [b], {"perm": (1, 0)})
    alpha = float(_parse(attrs.get("alpha"), 1.0))
    if alpha == 1.0:
        return ctx.add("MatMul", name, [a, b])
    m = ctx.add("MatMul", name + "_mm", [a, b])
    sname = name + "_alpha"
    ctx.extra_initializers[sname] = _np.asarray(alpha, dtype=_np.float32)
    return ctx.add("Mul", name, [m, sname])


@register("Crop")
def _crop(ctx, name, ins, attrs):
    # attr-form center/offset crop on H/W (reference Crop → Slice); the
    # 2-input crop-like form needs shapes, which the dict walk doesn't carry
    hw = _parse(attrs.get("h_w"), None)
    if hw is None or len(ins) > 1:
        raise NotImplementedError(
            "Crop: only the attr-form (h_w [+ offset], center_crop=False) "
            "exports")
    h, w = (int(x) for x in hw)
    off = _tuple2(_parse(attrs.get("offset"), (0, 0)), (0, 0))
    oy, ox = (int(x) for x in off)
    starts = _int64_init(ctx, name + "_starts", [oy, ox])
    ends = _int64_init(ctx, name + "_ends", [oy + h, ox + w])
    axes = _int64_init(ctx, name + "_axes", [2, 3])
    return ctx.add("Slice", name, [ins[0], starts, ends, axes])


def _flash_attention_cv(ctx, name, ins, attrs):
    """The attention kernels as the formula they compute, for a runtime
    that has no Pallas: softmax(q k^T * scale + key bias) v over (B, H, T,
    D).  Inference form: the dropout variant exports without its dropout,
    as ``Dropout`` itself does."""
    if _parse(attrs.get("causal"), False) in (True, 1, "True"):
        raise NotImplementedError(f"{name}: causal attention has no ONNX "
                                  f"export")
    scale = _parse(attrs.get("scale"), None)
    if scale is None:
        raise NotImplementedError(f"{name}: export needs scale= given (the "
                                  f"head width is not known here)")

    def const(tag, value):
        ctx.extra_initializers[f"{name}_{tag}"] = _np.asarray(
            value, dtype=_np.float32)
        return f"{name}_{tag}"

    q, k, v = ins[:3]
    kt = ctx.add("Transpose", name + "_kt", [k], {"perm": (0, 1, 3, 2)})
    s = ctx.add("MatMul", name + "_qk", [q, kt])
    s = ctx.add("Mul", name + "_scaled", [s, const("scale", float(scale))])
    if len(ins) > 3:
        # (B, T) 1 = valid -> (B, 1, 1, T) additive -1e30 on masked keys
        away = ctx.add("Sub", name + "_away", [const("one", 1.0), ins[3]])
        bias = ctx.add("Mul", name + "_bias", [away, const("neg", -1e30)])
        for i in (1, 2):        # one axis a node: what the importer reads
            axes = _int64_init(ctx, f"{name}_bias_axis{i}", [i])
            bias = ctx.add("Unsqueeze", f"{name}_bias{i + 2}", [bias, axes])
        s = ctx.add("Add", name + "_masked", [s, bias])
    p = ctx.add("Softmax", name + "_p", [s], {"axis": -1})
    return ctx.add("MatMul", name, [p, v])


register("_contrib_flash_attention")(_flash_attention_cv)
register("_contrib_flash_attention_dropout")(_flash_attention_cv)
