"""Aggregated (multi-tensor) optimizer update path (ISSUE 2 tentpole):
numerics parity with the per-parameter path, grouping/fallback rules,
state serialization compatibility, zero steady-state compile misses, and
the trainer/kvstore wiring."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu import optimizer as opt
from mxnet_tpu.optimizer import aggregate


@pytest.fixture(autouse=True)
def _clean_bus():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


SHAPES = [(4, 3), (7,), (2, 3, 2), (5, 5)]


def _updater_pair(name, **kwargs):
    """(per-param updater, aggregated updater) over the same config."""
    o1 = opt.create(name, **kwargs)
    o1.aggregate_num = 1            # forces the per-parameter path
    o2 = opt.create(name, **kwargs)
    assert o2.aggregate_num > 1     # default-on (env MXNET_OPTIMIZER_...)
    return opt.get_updater(o1), opt.get_updater(o2)


def _run_steps(updater, w_np, g_np, steps=3, dtype="float32"):
    ws = [nd.array(w.copy(), dtype=dtype) for w in w_np]
    idx = list(range(len(ws)))
    for _ in range(steps):
        gs = [nd.array(g.copy(), dtype=dtype) for g in g_np]
        updater(idx, gs, ws)
    return ws


@pytest.mark.parametrize("name,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.01}),
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "clip_gradient": 0.1}),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("signum", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adagrad", {"learning_rate": 0.1, "wd": 0.01}),
    ("adamax", {"learning_rate": 0.002, "wd": 0.01}),
    ("adamax", {"learning_rate": 0.002, "clip_gradient": 0.1}),
    ("nadam", {"learning_rate": 0.001, "wd": 0.01}),
    ("nadam", {"learning_rate": 0.001, "clip_gradient": 0.1,
               "schedule_decay": 0.01}),
    ("ftml", {"learning_rate": 0.01, "wd": 0.01}),
    ("ftml", {"learning_rate": 0.01, "clip_gradient": 0.1, "beta1": 0.7}),
    ("ftrl", {"learning_rate": 0.1, "wd": 0.01, "lamda1": 0.02}),
    ("ftrl", {"learning_rate": 0.1, "clip_gradient": 0.1, "beta": 0.5}),
])
def test_aggregated_matches_per_param(name, kwargs):
    np.random.seed(0)
    w_np = [np.random.rand(*s).astype(np.float32) for s in SHAPES]
    g_np = [(np.random.rand(*s).astype(np.float32) - 0.5) for s in SHAPES]
    u1, u2 = _updater_pair(name, **kwargs)
    ws1 = _run_steps(u1, w_np, g_np)
    ws2 = _run_steps(u2, w_np, g_np)
    # FTML's z update (b1*z + (1-b1)*g - sigma*w) cancels catastrophically,
    # amplifying the ulp-level rounding drift between the per-param op's
    # baked f64 python constants and the group's traced f32 scalars; every
    # other rule sits inside the tight tolerance
    rtol, atol = (2e-4, 1e-5) if name == "ftml" else (1e-5, 1e-6)
    for a, b in zip(ws1, ws2):
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                   rtol=rtol, atol=atol)
    # optimizer state (momentum/mean/var/...) matches too
    for i in u1.states:
        l1 = aggregate._state_leaves(u1.states[i])
        l2 = aggregate._state_leaves(u2.states[i])
        assert len(l1) == len(l2)
        for s1, s2 in zip(l1, l2):
            np.testing.assert_allclose(s1.asnumpy(), s2.asnumpy(),
                                       rtol=rtol, atol=atol)


def test_nadam_m_schedule_tracks_per_param():
    """Nadam's host-side momentum schedule is mutated once per parameter
    per update on the per-param path; the aggregated extras hook must
    replicate the recurrence exactly (ISSUE 5 satellite)."""
    np.random.seed(3)
    w_np = [np.random.rand(*s).astype(np.float32) for s in SHAPES]
    g_np = [(np.random.rand(*s).astype(np.float32) - 0.5) for s in SHAPES]
    o1 = opt.create("nadam", learning_rate=0.001)
    o1.aggregate_num = 1
    o2 = opt.create("nadam", learning_rate=0.001)
    u1, u2 = opt.get_updater(o1), opt.get_updater(o2)
    ws1 = _run_steps(u1, w_np, g_np, steps=4)
    ws2 = _run_steps(u2, w_np, g_np, steps=4)
    np.testing.assert_allclose(o1.m_schedule, o2.m_schedule, rtol=1e-12)
    for a, b in zip(ws1, ws2):
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_nadam_mixed_precision_takes_per_param_path():
    """Nadam's m_schedule snapshots are processing-ORDER-sensitive: mixed
    fp16(mp)+fp32 params split into two groups, which would permute the
    per-param index order (members 1 and 2 would swap schedule prefixes).
    The order_sensitive guard must route the whole update per-param, so
    results match the reference exactly."""
    np.random.seed(5)
    shapes = [(4, 3), (7,), (2, 3, 2), (5, 5)]
    dtypes = ["float32", "float16", "float32", "float16"]
    w_np = [np.random.rand(*s).astype(d) for s, d in zip(shapes, dtypes)]
    g_np = [(np.random.rand(*s).astype(d) - np.asarray(0.5, d))
            for s, d in zip(shapes, dtypes)]

    def run(agg):
        o = opt.create("nadam", learning_rate=0.001, multi_precision=True)
        o.aggregate_num = 64 if agg else 1
        u = opt.get_updater(o)
        ws = [nd.array(w.copy(), dtype=w.dtype) for w in w_np]
        idx = list(range(len(ws)))
        for _ in range(3):
            gs = [nd.array(g.copy(), dtype=g.dtype) for g in g_np]
            u(idx, gs, ws)
        return o, ws

    telemetry.enable()
    o1, ws1 = run(False)
    o2, ws2 = run(True)
    assert o1.m_schedule == o2.m_schedule
    for a, b in zip(ws1, ws2):
        assert np.array_equal(a.asnumpy(), b.asnumpy())
    # the guard shows up in telemetry: every member counted as fallback
    assert telemetry.counter_value("optimizer.fallback_params") \
        >= len(shapes)


def test_ftml_t_rides_in_extras_not_recompiles():
    """FTML's per-param op bakes the step count t into its attrs (one jit
    entry per t value); the aggregated rule must hand the bias corrections
    over as traced extras, so 5 steps + an lr change compile exactly once
    (ISSUE 6 satellite)."""
    aggregate.clear_cache()
    telemetry.reset()
    telemetry.enable()
    o = opt.create("ftml", learning_rate=0.01)
    ws = [nd.array(np.ones(s, np.float32)) for s in SHAPES]
    gs = [nd.array(np.full(s, 0.1, np.float32)) for s in SHAPES]
    u = opt.get_updater(o)
    idx = list(range(len(ws)))
    for step in range(5):
        if step == 3:
            o.set_learning_rate(0.005)
        u(idx, gs, ws)
    assert telemetry.counter_value("optimizer.compile_misses") == 1
    assert telemetry.counter_value("optimizer.fallback_params") == 0


def test_adamax_nadam_zero_steady_state_misses():
    """Both new rules ride the compiled-group cache: step 1 compiles,
    later steps (and lr changes) add zero compile misses."""
    for name in ("adamax", "nadam", "ftml", "ftrl"):
        aggregate.clear_cache()   # group sigs may be warm from other tests
        telemetry.reset()
        telemetry.enable()
        o = opt.create(name)
        ws = [nd.array(np.ones(s, np.float32)) for s in SHAPES]
        gs = [nd.array(np.ones(s, np.float32)) for s in SHAPES]
        u = opt.get_updater(o)
        idx = list(range(len(ws)))
        u(idx, gs, ws)
        misses = telemetry.counter_value("optimizer.compile_misses")
        assert misses >= 1, name
        for _ in range(3):
            u(idx, gs, ws)
        o.set_learning_rate(0.5)
        u(idx, gs, ws)
        assert telemetry.counter_value("optimizer.compile_misses") \
            == misses, name
        assert telemetry.counter_value("optimizer.fallback_params") == 0, \
            name


def test_multi_precision_fp16_master_path():
    """fp16 weights + multi_precision: the aggregated path keeps the fp32
    master in the state tuple and casts back, exactly like the generic
    per-param wrap."""
    np.random.seed(1)
    w_np = [np.random.rand(*s).astype(np.float16) for s in SHAPES[:3]]
    g_np = [(np.random.rand(*s).astype(np.float16) - 0.5)
            for s in SHAPES[:3]]
    u1, u2 = _updater_pair("sgd", learning_rate=0.1, momentum=0.9,
                           wd=0.01, multi_precision=True)
    ws1 = _run_steps(u1, w_np, g_np, dtype="float16")
    ws2 = _run_steps(u2, w_np, g_np, dtype="float16")
    for a, b in zip(ws1, ws2):
        assert a.dtype == np.float16 and b.dtype == np.float16
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                   rtol=1e-3, atol=1e-3)
    # fp32 masters agree to fp32 tolerance
    for i in u1.states:
        m1, m2 = u1.states[i][0], u2.states[i][0]
        assert m1.dtype == np.float32 and m2.dtype == np.float32
        np.testing.assert_allclose(m1.asnumpy(), m2.asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_bare_fp16_falls_back():
    """fp16 without multi_precision keeps the (warning) per-param path."""
    o = opt.SGD(learning_rate=0.1, momentum=0.9)
    w = [nd.array(np.ones((3,), np.float16), dtype="float16")
         for _ in range(2)]
    g = [nd.array(np.ones((3,), np.float16), dtype="float16")
         for _ in range(2)]
    telemetry.enable()
    with pytest.warns(UserWarning):
        u = opt.get_updater(o)
        u([0, 1], g, w)
    snap = telemetry.snapshot()
    assert snap["counters"].get("optimizer.fallback_params", 0) == 2
    assert snap["counters"].get("optimizer.aggregated_params", 0) == 0


def test_unsupported_optimizer_falls_back():
    """No registered rule (e.g. AdaDelta) → per-param updates, same math."""
    np.random.seed(2)
    w_np = [np.random.rand(4, 3).astype(np.float32) for _ in range(3)]
    g_np = [np.random.rand(4, 3).astype(np.float32) for _ in range(3)]
    u1, u2 = _updater_pair("adadelta")
    ws1 = _run_steps(u1, w_np, g_np)
    ws2 = _run_steps(u2, w_np, g_np)
    for a, b in zip(ws1, ws2):
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy(), rtol=1e-6)


def test_subclass_is_not_aggregated():
    """A user subclass may override update(); exact-class match only."""

    class MySGD(opt.SGD):
        def _update_impl(self, index, weight, grad, state,
                         multi_precision=False):
            weight[:] = weight - 1.0    # nothing like SGD on purpose

    telemetry.enable()
    o = MySGD(learning_rate=0.1)
    u = opt.get_updater(o)
    ws = [nd.array(np.zeros((3,), np.float32)) for _ in range(2)]
    gs = [nd.array(np.zeros((3,), np.float32)) for _ in range(2)]
    u([0, 1], gs, ws)
    for w in ws:
        np.testing.assert_allclose(w.asnumpy(), -np.ones(3))
    assert telemetry.counter_value("optimizer.aggregated_params") == 0
    assert telemetry.counter_value("optimizer.fallback_params") == 2


@pytest.mark.parametrize("shapes,cap,dispatches", [
    ([(3,)] * 10, 4, 3),                      # ceil(10 / 4)
    # a ResNet-like census, 66 (kernel, gamma, beta) trios and the
    # classifier pair: 200 tensors of mixed shapes update in ONE dispatch
    # (the per-parameter path takes one a tensor)
    ([(8, 4, 3, 3), (8,), (8,)] * 66 + [(10, 8), (10,)], 256, 1),
], ids=["same_shape_cap4", "resnet_like_200"])
def test_aggregation_size_chunks_groups(shapes, cap, dispatches):
    """MXNET_OPTIMIZER_AGGREGATION_SIZE caps tensors per dispatch."""
    telemetry.enable()
    o = opt.SGD(learning_rate=0.1, momentum=0.9)
    o.aggregate_num = cap
    n = len(shapes)
    ws = [nd.array(np.ones(s, np.float32)) for s in shapes]
    gs = [nd.array(np.ones(s, np.float32)) for s in shapes]
    c0 = telemetry.counter_value("optimizer.update_calls")
    u = opt.get_updater(o)
    u(list(range(n)), gs, ws)
    assert telemetry.counter_value("optimizer.update_calls") - c0 \
        == dispatches


def test_sparse_grad_falls_back():
    """Compressed row-sparse grads keep the O(nnz) lazy per-param kernels."""
    from mxnet_tpu.ndarray import sparse as sp
    telemetry.enable()
    o = opt.SGD(learning_rate=0.1, momentum=0.9)
    dense_w = nd.array(np.ones((4, 3), np.float32))
    sparse_w = nd.array(np.ones((6, 3), np.float32))
    rs = sp.row_sparse_array(
        (np.ones((2, 3), np.float32), np.array([1, 4])), shape=(6, 3))
    u = opt.get_updater(o)
    u([0, 1], [nd.array(np.ones((4, 3), np.float32)), rs],
      [dense_w, sparse_w])
    snap = telemetry.snapshot()
    assert snap["counters"].get("optimizer.fallback_params", 0) == 1
    assert snap["counters"].get("optimizer.aggregated_params", 0) == 1
    # the sparse fallback updated the touched rows and only those
    out = sparse_w.asnumpy()
    assert not np.allclose(out[1], 1.0)
    assert np.allclose(out[0], 1.0)
    # the dense member went through the aggregated path
    assert not np.allclose(dense_w.asnumpy(), 1.0)


def test_zero_compile_misses_steady_state():
    """After the first step compiles each group, later steps replay the
    cached executable: the group-signature compile-miss counter freezes
    (ISSUE 2 acceptance: zero recompiles after step 1)."""
    telemetry.enable()
    o = opt.Adam(learning_rate=0.01)
    ws = [nd.array(np.ones(s, np.float32)) for s in SHAPES]
    gs = [nd.array(np.ones(s, np.float32)) for s in SHAPES]
    u = opt.get_updater(o)
    idx = list(range(len(ws)))
    u(idx, gs, ws)
    misses_after_1 = telemetry.counter_value("optimizer.compile_misses")
    for _ in range(4):
        u(idx, gs, ws)
    assert telemetry.counter_value("optimizer.compile_misses") \
        == misses_after_1
    snap = telemetry.snapshot()
    assert snap["gauges"]["optimizer.update_groups"] >= 1
    assert snap["gauges"]["optimizer.state_bytes"] > 0
    # lr changes are traced, not baked: no recompile either
    o.set_learning_rate(0.5)
    u(idx, gs, ws)
    assert telemetry.counter_value("optimizer.compile_misses") \
        == misses_after_1


def test_group_update_spans_inside_trainer_update():
    """trainer.update gets optimizer.update_group sub-spans per group."""
    telemetry.enable()
    x = gluon.Parameter("x", shape=(4,))
    y = gluon.Parameter("y", shape=(2, 2))
    for p in (x, y):
        p.initialize(init="zeros")
    trainer = gluon.Trainer([x, y], "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    with mx.autograd.record():
        (x.data().sum() + y.data().sum()).backward()
    trainer.step(1)
    spans = telemetry.span_aggregates()
    assert "trainer.update" in spans
    assert "optimizer.update_group" in spans
    names = [e[1] for e in telemetry.bus.events()]
    assert "optimizer.update_group" in names


def _make_trainer(agg):
    net_x = gluon.Parameter("w", shape=(6, 4))
    net_x.initialize(init="ones")
    trainer = gluon.Trainer([net_x], "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 0.01})
    if not agg:
        trainer._optimizer.aggregate_num = 1
    return net_x, trainer


def _step(p, trainer):
    with mx.autograd.record():
        ((p.data() * 1.5) ** 2).sum().backward()
    trainer.step(1)


def test_trainer_save_load_states_cross_path(tmp_path):
    """States saved by the aggregated updater load into a per-param
    trainer (and vice versa) and continue the identical trajectory —
    the ser/de format is path-independent."""
    pa, ta = _make_trainer(agg=True)
    pp, tp = _make_trainer(agg=False)
    for _ in range(3):
        _step(pa, ta)
        _step(pp, tp)
    np.testing.assert_allclose(pa.data().asnumpy(), pp.data().asnumpy(),
                               rtol=1e-5, atol=1e-6)
    fa = str(tmp_path / "agg.states")
    fp = str(tmp_path / "pp.states")
    ta.save_states(fa)
    tp.save_states(fp)

    # structural equality of the serialized states
    import pickle
    sa = pickle.loads(open(fa, "rb").read())[0]
    sp_ = pickle.loads(open(fp, "rb").read())[0]
    assert sorted(sa) == sorted(sp_)
    for k in sa:
        assert type(sa[k]) is type(sp_[k])
        np.testing.assert_allclose(sa[k].asnumpy(), sp_[k].asnumpy(),
                                   rtol=1e-5, atol=1e-6)

    # cross-load: aggregated states into the per-param trainer and
    # per-param states into the aggregated trainer; trajectories converge
    tp.load_states(fa)
    ta.load_states(fp)
    for _ in range(2):
        _step(pa, ta)
        _step(pp, tp)
    np.testing.assert_allclose(pa.data().asnumpy(), pp.data().asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_trainer_aggregated_matches_per_param_trajectory():
    pa, ta = _make_trainer(agg=True)
    pp, tp = _make_trainer(agg=False)
    for _ in range(5):
        _step(pa, ta)
        _step(pp, tp)
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pp.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_kvstore_batched_push_aggregates():
    """A multi-key push with a server-side optimizer takes ONE aggregated
    dispatch (the kvstore _updater wiring)."""
    telemetry.enable()
    kv = mx.kv.create("local")
    o = opt.SGD(learning_rate=0.1, momentum=0.9)
    kv.set_optimizer(o)
    n = 6
    for i in range(n):
        kv.init(i, nd.array(np.ones((3, 2), np.float32)))
    c0 = telemetry.counter_value("optimizer.update_calls")
    kv.push(list(range(n)),
            [nd.array(np.ones((3, 2), np.float32)) for _ in range(n)])
    assert telemetry.counter_value("optimizer.update_calls") - c0 == 1
    out = nd.array(np.zeros((3, 2), np.float32))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), 1.0 - 0.1, rtol=1e-6)


def test_kvstore_custom_updater_keeps_per_key_contract():
    """set_updater with a plain function: one call per key, unchanged."""
    calls = []
    kv = mx.kv.create("local")
    for i in range(3):
        kv.init(i, nd.array(np.zeros((2,), np.float32)))
    kv.set_updater(lambda k, recv, stored: calls.append(k))
    kv.push([0, 1, 2],
            [nd.array(np.ones((2,), np.float32)) for _ in range(3)])
    assert calls == [0, 1, 2]


def test_module_update_uses_aggregated_path():
    telemetry.enable()
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc1")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (5, 6))],
             label_shapes=[("softmax_label", (5,))])
    mod.init_params()
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),
                                         ("momentum", 0.9)))
    batch = mx.io.DataBatch(
        data=[nd.array(np.random.rand(5, 6).astype("float32"))],
        label=[nd.array(np.zeros(5, "float32"))])
    c0 = telemetry.counter_value("optimizer.update_calls")
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    # 4 param tensors (2x weight+bias) -> grouped dispatches, not 4
    delta = telemetry.counter_value("optimizer.update_calls") - c0
    assert 1 <= delta < 4
    assert telemetry.counter_value("optimizer.aggregated_params") == 4


def test_checkpoint_spans_for_trainer_states(tmp_path):
    """checkpoint.save / checkpoint.restore spans carry bytes and the
    serialize-vs-IO split (ISSUE 2 satellite)."""
    telemetry.enable()
    p, tr = _make_trainer(agg=True)
    _step(p, tr)
    f = str(tmp_path / "t.states")
    tr.save_states(f)
    tr.load_states(f)
    spans = telemetry.span_aggregates()
    for name in ("checkpoint.save", "checkpoint.restore",
                 "checkpoint.serialize", "checkpoint.io",
                 "checkpoint.deserialize"):
        assert name in spans, (name, sorted(spans))
    evs = {e[1]: e for e in telemetry.bus.events()}
    import os
    assert evs["checkpoint.save"][6]["bytes_written"] \
        == os.path.getsize(f)
    assert evs["checkpoint.restore"][6]["bytes_read"] \
        == os.path.getsize(f)


def test_aggregate_disabled_by_env_value_one():
    """aggregate_num <= 1 (MXNET_OPTIMIZER_AGGREGATION_SIZE=1) disables
    grouping entirely."""
    telemetry.enable()
    o = opt.SGD(learning_rate=0.1)
    o.aggregate_num = 1
    u = opt.get_updater(o)
    assert not u.aggregate_updates
    ws = [nd.array(np.ones((2,), np.float32)) for _ in range(3)]
    gs = [nd.array(np.ones((2,), np.float32)) for _ in range(3)]
    u([0, 1, 2], gs, ws)
    assert telemetry.counter_value("optimizer.aggregated_params") == 0
    for w in ws:
        np.testing.assert_allclose(w.asnumpy(), 0.9, rtol=1e-6)


def test_clip_gradient_zero_is_a_noop_like_per_param():
    """clip_gradient=0.0 (or negative) never clips on the per-param path
    (truthiness / >0 gates) — the aggregated path must match, not clamp
    every gradient to zero."""
    for clip in (0.0, -1.0):
        w_np = [np.full((3,), 1.0, np.float32) for _ in range(2)]
        g_np = [np.full((3,), 0.5, np.float32) for _ in range(2)]
        u1, u2 = _updater_pair("sgd", learning_rate=0.1, momentum=0.9,
                               clip_gradient=clip)
        ws1 = _run_steps(u1, w_np, g_np, steps=2)
        ws2 = _run_steps(u2, w_np, g_np, steps=2)
        for a, b in zip(ws1, ws2):
            np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                       rtol=1e-6)
            assert not np.allclose(b.asnumpy(), 1.0), \
                "clip_gradient=%r froze the weights" % clip


def test_mixed_device_params_group_per_device():
    """Parameters living on different devices must not fuse into one jit
    call (committed-device conflict); each device gets its own group."""
    import jax
    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs the virtual multi-device CPU mesh")
    telemetry.enable()
    o = opt.SGD(learning_rate=0.1, momentum=0.9)
    ws, gs = [], []
    for i in range(4):
        dev = devs[i % 2]
        ws.append(mx.nd.NDArray(jax.device_put(
            np.ones((3,), np.float32), dev)))
        gs.append(mx.nd.NDArray(jax.device_put(
            np.full((3,), 0.5, np.float32), dev)))
    u = opt.get_updater(o)
    c0 = telemetry.counter_value("optimizer.update_calls")
    u([0, 1, 2, 3], gs, ws)
    # 2 devices -> 2 groups, both aggregated
    assert telemetry.counter_value("optimizer.update_calls") - c0 == 2
    assert telemetry.counter_value("optimizer.aggregated_params") == 4
    for w in ws:
        np.testing.assert_allclose(w.asnumpy(), 1.0 - 0.05, rtol=1e-6)


def test_updater_aggregate_updates_is_assignable():
    """Reference parity: `updater.aggregate_updates = False` disables the
    batched path without touching the optimizer."""
    o = opt.SGD(learning_rate=0.1)
    u = opt.get_updater(o)
    assert u.aggregate_updates
    u.aggregate_updates = False
    assert not u.aggregate_updates
    telemetry.enable()
    ws = [nd.array(np.ones((2,), np.float32)) for _ in range(3)]
    gs = [nd.array(np.ones((2,), np.float32)) for _ in range(3)]
    u([0, 1, 2], gs, ws)
    assert telemetry.counter_value("optimizer.aggregated_params") == 0
    for w in ws:
        np.testing.assert_allclose(w.asnumpy(), 0.9, rtol=1e-6)
    u.aggregate_updates = True
    u([0, 1, 2], gs, ws)
    assert telemetry.counter_value("optimizer.aggregated_params") == 3
