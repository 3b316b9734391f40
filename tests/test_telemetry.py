"""Telemetry subsystem: event bus, exporters, instrumentation, profiler
integration (ISSUE 1 tentpole + satellites)."""
import json
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import bus


@pytest.fixture(autouse=True)
def _clean_bus():
    """Every test starts with a fresh, disabled bus and leaves it that way."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# ------------------------------------------------------------------ bus core
def test_enable_disable():
    assert not telemetry.is_enabled()
    telemetry.enable()
    assert telemetry.is_enabled()
    assert telemetry.count("t.c") == 1
    telemetry.disable()
    assert not telemetry.is_enabled()
    # disabled: count is a no-op returning 0, value survives
    assert telemetry.count("t.c") == 0
    assert telemetry.counter_value("t.c") == 1
    # reset drops state
    telemetry.reset()
    assert telemetry.counter_value("t.c") == 0


def test_counter_math_and_labels():
    telemetry.enable()
    telemetry.count("k.calls")
    telemetry.count("k.calls", 4)
    telemetry.count("k.bytes", 2.5)        # float-valued counters (ms, etc.)
    telemetry.count("k.calls", 2, op="add")
    telemetry.count("k.calls", 3, op="mul")
    snap = telemetry.snapshot()
    assert snap["counters"]["k.calls"] == 10
    assert snap["counters"]["k.bytes"] == 2.5
    by_label = snap["counters_by_label"]["k.calls"]
    assert by_label['{op="add"}'] == 2
    assert by_label['{op="mul"}'] == 3


def test_gauge_and_snapshot_shape():
    telemetry.enable()
    telemetry.gauge("g.depth", 7)
    snap = telemetry.snapshot()
    assert snap["enabled"] is True
    assert snap["gauges"]["g.depth"] == 7
    for key in ("counters", "counters_by_label", "gauges", "spans",
                "n_events"):
        assert key in snap


def test_span_nesting():
    telemetry.enable()
    with telemetry.span("outer.scope", tag="a"):
        with telemetry.span("inner.scope"):
            pass
        with telemetry.span("inner.scope"):
            pass
    evs = [e for e in telemetry.trace_events() if e.get("ph") == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["inner.scope"]) == 2
    (outer,) = by_name["outer.scope"]
    assert outer["args"] == {"tag": "a"}
    # children nest inside the parent on the timeline (same thread)
    for child in by_name["inner.scope"]:
        assert child["ts"] >= outer["ts"]
        assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    agg = telemetry.span_aggregates()
    assert agg["inner.scope"][0] == 2
    assert agg["outer.scope"][1] >= agg["inner.scope"][1]


def test_span_noop_when_disabled():
    sp = telemetry.span("never.recorded")
    with sp:
        pass
    assert telemetry.snapshot()["spans"] == {}
    assert sp.set(x=1) is sp               # no-op span keeps the API


def test_ring_buffer_bounded():
    telemetry.enable(capacity=64)
    try:
        for i in range(200):
            telemetry.instant("flood.event", i=i)
        evs = telemetry.bus.events()
        assert len(evs) == 64
        # oldest dropped, newest kept
        assert evs[-1][6]["i"] == 199
    finally:
        telemetry.enable(capacity=telemetry.bus.DEFAULT_CAPACITY)


def test_trace_json_schema():
    telemetry.enable()
    with telemetry.span("sub.work", n=1):
        telemetry.instant("sub.tick")
    telemetry.counter_sample("sub.count", 42)
    doc = telemetry.dump_trace()
    # chrome://tracing loadability: valid JSON object with a traceEvents
    # list whose entries carry name/ph/ts/pid/tid (and dur for X phases)
    doc = json.loads(json.dumps(doc))
    assert isinstance(doc["traceEvents"], list)
    phases = set()
    for e in doc["traceEvents"]:
        assert "name" in e and "ph" in e and "pid" in e
        if e["ph"] != "M":
            assert "ts" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
        phases.add(e["ph"])
    assert {"X", "i", "C", "M"} <= phases


def test_dump_trace_writes_file(tmp_path):
    telemetry.enable()
    with telemetry.span("a.b"):
        pass
    path = tmp_path / "trace.json"
    telemetry.dump_trace(str(path))
    doc = json.loads(path.read_text())
    assert any(e["name"] == "a.b" for e in doc["traceEvents"])


def test_dump_metrics_prometheus_format():
    telemetry.enable()
    telemetry.count("m.calls", 3, op="add")
    telemetry.gauge("m.depth", 2)
    with telemetry.span("m.step"):
        pass
    text = telemetry.dump_metrics()
    assert "# TYPE mxnet_m_calls counter" in text
    assert "mxnet_m_calls 3" in text
    assert 'mxnet_m_calls{op="add"} 3' in text
    assert "# TYPE mxnet_m_depth gauge" in text
    assert "mxnet_m_depth 2" in text
    assert "mxnet_m_step_calls 1" in text


# ------------------------------------------------------- instrumented paths
def test_eager_dispatch_counters(monkeypatch):
    # the eager jit cache is process-wide: a third distinct scalar seen by
    # `_mul_scalar` anywhere earlier on this worker moves the op to the
    # no-jit set, and a warm entry turns the first call into a hit.  Start
    # from empty caches so the counts do not depend on what ran before.
    from mxnet_tpu.ndarray import ndarray as nd_mod
    monkeypatch.setattr(nd_mod, "_EAGER_JIT", {})
    monkeypatch.setattr(nd_mod, "_EAGER_NOJIT", set())
    monkeypatch.setattr(nd_mod, "_EAGER_MISSES", {})
    telemetry.enable()
    x = mx.nd.ones((4, 4))
    for _ in range(3):
        y = x * 3.0
    y.wait_to_read()
    snap = telemetry.snapshot()
    c = snap["counters"]
    assert c["dispatch.op_calls"] >= 3
    # first _mul_scalar call compiles (miss), later ones hit the cache
    assert c["dispatch.jit_cache_misses"] >= 1
    assert c.get("dispatch.jit_cache_hits", 0) >= 2
    labeled = snap["counters_by_label"]["dispatch.op_calls"]
    assert any("_mul_scalar" in k for k in labeled)


def test_cachedop_recompile_events():
    telemetry.enable()
    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 5)))
    net(mx.nd.ones((2, 5)))            # same signature: cache hit
    net(mx.nd.ones((7, 5)))            # new batch shape: silent recompile
    snap = telemetry.snapshot()
    assert snap["counters"]["cachedop.recompiles"] == 2
    assert snap["counters"]["cachedop.cache_hits"] == 1
    assert snap["counters"]["cachedop.calls"] == 3
    recs = [e for e in telemetry.trace_events()
            if e["name"] == "cachedop.recompile"]
    assert len(recs) == 2
    shapes = {e["args"]["shapes"] for e in recs}
    assert shapes == {"((2, 5),)", "((7, 5),)"}
    assert all("training" in e["args"] for e in recs)


def test_cachedop_no_false_recompile_on_late_enable():
    """Enabling telemetry AFTER warmup (attach to a running job) must not
    report already-compiled signatures as fresh recompiles."""
    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 5)))           # compiled with the bus off
    telemetry.enable()
    net(mx.nd.ones((2, 5)))           # same signature: a hit, not a compile
    snap = telemetry.snapshot()
    assert snap["counters"].get("cachedop.recompiles", 0) == 0
    assert snap["counters"]["cachedop.cache_hits"] == 1


def test_kvstore_row_sparse_push_bytes():
    """Compressed row-sparse pushes bill the nnz payload, not the dense
    shape."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.sparse import RowSparseNDArray
    telemetry.enable()
    kv = mx.kv.create("local")
    kv.init("emb", mx.nd.zeros((1000, 4)))
    grad = RowSparseNDArray.from_rows(
        jnp.asarray([3, 7], jnp.int32),
        jnp.ones((2, 4), jnp.float32), (1000, 4))
    kv.push("emb", grad)
    c = telemetry.snapshot()["counters"]
    # 2x4 f32 values + 2 int32 indices = 32 + 8, nowhere near 16000
    assert c["kvstore.push_bytes"] == 2 * 4 * 4 + 2 * 4


def test_kvstore_counters():
    telemetry.enable()
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.ones((4, 2)))
    kv.push("w", mx.nd.ones((4, 2)))
    out = mx.nd.zeros((4, 2))
    kv.pull("w", out=out)
    c = telemetry.snapshot()["counters"]
    assert c["kvstore.init_calls"] == 1
    assert c["kvstore.push_calls"] == 1
    assert c["kvstore.pull_calls"] == 1
    assert c["kvstore.push_bytes"] == 4 * 2 * 4
    assert c["kvstore.pull_bytes"] == 4 * 2 * 4


def test_io_prefetch_wait_counters():
    telemetry.enable()
    data = np.random.rand(32, 3).astype("float32")
    label = np.arange(32, dtype="float32")
    it = mx.io.NDArrayIter(data, label, batch_size=8)
    pit = mx.io.PrefetchingIter(it)
    n = sum(1 for _ in pit)
    assert n == 4
    c = telemetry.snapshot()["counters"]
    assert c["io.batches"] >= 4
    assert "io.consumer_wait_ms" in c
    assert "io.producer_wait_ms" in c


def test_device_prefetch_iter_counters():
    telemetry.enable()
    data = np.random.rand(16, 3).astype("float32")
    it = mx.io.NDArrayIter(data, np.zeros(16, "float32"), batch_size=8)
    pit = mx.io.DevicePrefetchIter(it, lambda b: b.data[0].asnumpy())
    n = sum(1 for _ in pit)
    assert n == 2
    c = telemetry.snapshot()["counters"]
    assert c["io.batches"] >= 2
    assert "io.consumer_wait_ms" in c
    spans = telemetry.snapshot()["spans"]
    assert spans["io.stage_batch"]["calls"] >= 2


def test_engine_bulk_observable():
    telemetry.enable()
    with mx.engine.bulk(8):
        y = mx.nd.ones((2, 2)) + 1.0
        y = y * 2.0
    y.wait_to_read()
    snap = telemetry.snapshot()
    assert snap["counters"]["engine.bulk_scopes"] == 1
    (ev,) = [e for e in telemetry.trace_events()
             if e["name"] == "engine.bulk"]
    assert ev["args"]["size"] == 8
    assert ev["args"]["ops_in_scope"] >= 2


def test_gluon_trainer_step_span():
    telemetry.enable()
    net = mx.gluon.nn.Dense(2)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    with mx.autograd.record():
        loss = net(mx.nd.ones((4, 3))).sum()
    loss.backward()
    trainer.step(4)
    snap = telemetry.snapshot()
    assert snap["counters"]["trainer.steps"] == 1
    assert snap["spans"]["trainer.step"]["calls"] == 1
    assert snap["spans"]["trainer.update"]["calls"] == 1


def test_spmd_trainer_telemetry():
    telemetry.enable()
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.nd.ones((8, 4)))
    mesh = make_mesh(n_devices=2, dp=2)
    tr = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd", mesh)
    x = np.random.rand(8, 4).astype("float32")
    y = np.random.rand(8, 4).astype("float32")
    tr.step(x, y)
    tr.step(x, y)
    snap = telemetry.snapshot()
    assert snap["counters"]["trainer.steps"] == 2
    assert snap["spans"]["trainer.step"]["calls"] == 2
    assert snap["gauges"]["trainer.donated_bytes"] > 0
    # dp=2 data-parallel grads force a psum in the lowered step
    assert snap["gauges"]["trainer.collective_ops"] >= 1
    assert snap["gauges"]["trainer.collective_bytes"] > 0


def test_collective_stats_parser():
    text = """
      %0 = "stablehlo.all_reduce"(%arg0) : (tensor<8x4xf32>) -> tensor<8x4xf32>
      %1 = stablehlo.add %a, %b : tensor<2xf32>
      %2 = "stablehlo.all_gather"(%arg1) : (tensor<16xbf16>) -> tensor<64xbf16>
    """
    n, nbytes = telemetry.collective_stats(text)
    assert n == 2
    # all_reduce: 8*4*4 = 128; all_gather: max(16*2, 64*2) = 128
    assert nbytes == 128 + 128


def test_collective_stats_region_and_hlo_forms():
    # real StableHLO prints all_reduce with a reducer REGION: the payload
    # type sits on the closing line, and the scalar body must not bill
    region = '''
      %3 = "stablehlo.all_reduce"(%2) <{replica_groups = dense<0> : tensor<1x1xi64>}> ({
      ^bb0(%arg4: tensor<f32>, %arg5: tensor<f32>):
        %9 = stablehlo.add %arg4, %arg5 : tensor<f32>
        stablehlo.return %9 : tensor<f32>
      }) : (tensor<128x64xf32>) -> tensor<128x64xf32>
    '''
    n, nbytes = telemetry.collective_stats(region)
    assert (n, nbytes) == (1, 128 * 64 * 4)
    # post-compile HLO form: collective used later as a fusion OPERAND
    # must not double-count
    hlo = """
      %all-reduce.1 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %dot.1), channel_id=2
      %fus = f32[4,4]{1,0} fusion(f32[4,4]{1,0} %p, f32[4,4]{1,0} %all-reduce.1), kind=kLoop
    """
    n, nbytes = telemetry.collective_stats(hlo)
    assert (n, nbytes) == (1, 64)


def test_snapshot_usable_disabled():
    snap = telemetry.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"] == {}


# --------------------------------------------------- profiler integration
def test_profiler_counter_in_dumps():
    from mxnet_tpu import profiler
    domain = profiler.Domain("tel_test")
    c = profiler.Counter(domain, "requests", 5)
    c.increment(2)
    c += 3
    out = profiler.dumps()
    assert "Counters" in out
    assert "tel_test::requests" in out
    assert "10" in out


def test_profiler_marker_in_dumps():
    from mxnet_tpu import profiler
    domain = profiler.Domain("tel_test2")
    m = profiler.Marker(domain, "tick")
    m.mark()
    m.mark()
    out = profiler.dumps()
    assert "tel_test2::tick" in out


def test_profiler_dumps_sort_and_reset():
    from mxnet_tpu import profiler
    profiler._aggregate.clear()
    profiler._aggregate["zzz"] = (1, 0.5)
    profiler._aggregate["aaa"] = (3, 0.1)
    out = profiler.dumps(sort_by="total")
    assert out.index("zzz") < out.index("aaa")
    out = profiler.dumps(sort_by="count", ascending=True)
    # annotation section is total-sorted; sort_by applies to the device
    # table, but reset must clear the aggregates either way
    out = profiler.dumps(reset=True)
    assert "zzz" in out
    assert "zzz" not in profiler.dumps()
    assert profiler._aggregate == {}


def test_profiler_dumps_telemetry_section():
    telemetry.enable()
    with telemetry.span("myframe.step"):
        pass
    telemetry.count("myframe.counter", 9)
    from mxnet_tpu import profiler
    out = profiler.dumps()
    assert "Framework events (telemetry)" in out
    assert "myframe.step" in out
    assert "myframe.counter" in out


def test_monitor_telemetry_rows():
    telemetry.enable()
    telemetry.count("net.recompiles", 2)
    mon = mx.Monitor(1, pattern=".*")
    mon.tic()
    rows = mon.toc()
    assert ("telemetry:net.recompiles", "2") in \
        [(k, v) for _n, k, v in rows]
    # disabled bus: no telemetry rows in the stat stream
    telemetry.disable()
    mon.tic()
    assert all(not k.startswith("telemetry:") for _, k, _ in mon.toc())


def test_trace_has_multisubsystem_events():
    """The acceptance-criteria shape: one hybridized train step produces
    trace events from >= 4 subsystems."""
    telemetry.enable()
    net = mx.gluon.nn.Dense(2)
    net.initialize()
    net.hybridize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    kv = mx.kv.create("local")
    kv.init("aux", mx.nd.ones((2, 2)))
    kv.push("aux", mx.nd.ones((2, 2)))
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(np.ones((8, 3), "float32"),
                          np.zeros(8, "float32"), batch_size=8))
    for batch in it:
        with mx.autograd.record():
            loss = net(batch.data[0]).sum()
        loss.backward()
        trainer.step(8)
    doc = telemetry.dump_trace()
    cats = {e.get("cat") for e in doc["traceEvents"]} - {None}
    assert {"cachedop", "trainer", "kvstore", "io"} <= cats
    assert any(e["name"] == "cachedop.recompile"
               for e in doc["traceEvents"])


# ------------------------------------------------- background counter sampler
def test_counter_sampler_produces_timeline():
    """The opt-in sampler thread emits periodic 'C' samples so long runs
    get counter timelines in the chrome trace (ISSUE 2 satellite)."""
    import time as _time

    telemetry.enable()
    telemetry.count("samp.work", 5)
    telemetry.start_counter_sampler(["samp.work"], interval_ms=5)
    try:
        assert telemetry.sampler_running()
        _time.sleep(0.1)
    finally:
        telemetry.stop_counter_sampler()
    assert not telemetry.sampler_running()
    samples = [e for e in telemetry.bus.events()
               if e[0] == "C" and e[1] == "samp.work"]
    assert len(samples) >= 2
    assert all(e[6]["value"] == 5 for e in samples)
    # timeline appears in the exported chrome trace as counter events
    doc = telemetry.dump_trace()
    cevents = [e for e in doc["traceEvents"]
               if e.get("ph") == "C" and e.get("name") == "samp.work"]
    assert len(cevents) >= 2


def test_counter_sampler_all_counters_and_pause():
    """names=None samples every live counter; a disabled bus pauses the
    timeline without stopping the thread."""
    import time as _time

    telemetry.enable()
    telemetry.count("samp.a")
    telemetry.count("samp.b", 3)
    telemetry.start_counter_sampler(interval_ms=5)
    try:
        _time.sleep(0.05)
        names = {e[1] for e in telemetry.bus.events() if e[0] == "C"}
        assert {"samp.a", "samp.b"} <= names
        telemetry.disable()
        _time.sleep(0.03)
        n_disabled = len([e for e in telemetry.bus.events()
                          if e[0] == "C"])
        _time.sleep(0.05)
        assert len([e for e in telemetry.bus.events()
                    if e[0] == "C"]) == n_disabled
        telemetry.enable()
        _time.sleep(0.05)
        assert len([e for e in telemetry.bus.events()
                    if e[0] == "C"]) > n_disabled
    finally:
        telemetry.stop_counter_sampler()


# ------------------------------------------- bus spans on the profiler's clock
def test_span_off_builds_no_annotation(monkeypatch):
    """With the bus off ``span()`` hands out the one shared no-op and never
    reaches the profiler: the untraced hot path constructs nothing."""
    from mxnet_tpu.telemetry import bus
    built = []
    monkeypatch.setattr(bus, "annotation",
                        lambda *a, **k: built.append(a) or None)
    sp = telemetry.span("x.off", rows=3)
    assert sp is bus._NOOP and telemetry.span("x.other") is sp
    with sp as inner:
        inner.set(late=1)
    assert built == [] and bus._NOOP.attrs == {}
    telemetry.enable()
    with telemetry.span("x.on", rows=3):
        pass
    assert [a[0] for a in built] == ["x.on"]


def test_span_is_a_profiler_annotation_on_its_own_thread(tmp_path):
    """With the bus on and a ``jax.profiler`` session open, a span sits in
    the ``/host:CPU`` plane under its own name, with its scalar attrs, on
    the line of the thread that ran it; ``profiler._Scope`` goes through the
    same helper."""
    import glob
    import threading
    import time

    import jax
    telemetry.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        def work():
            with telemetry.span("toy.outer", rows=3, model="m",
                                shape=(1, 2)) as sp:
                sp.set(late=5)
                with telemetry.span("toy.inner"):
                    time.sleep(0.005)
        th = threading.Thread(target=work)
        th.start()
        th.join(30)
        assert not th.is_alive()
        with telemetry.span("toy.main_thread"):
            pass
        with mx.profiler.Event("toy_user_event"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = [p for p in jax.profiler.ProfileData.from_file(path).planes
            if p.name == "/host:CPU"][0]
    lines = {}
    for i, line in enumerate(host.lines):
        for ev in line.events:
            if ev.name.startswith(("toy.", "toy_")):
                lines.setdefault(i, {})[ev.name] = (
                    ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats))
    by_names = {frozenset(v): v for v in lines.values()}
    worker = by_names[frozenset({"toy.outer", "toy.inner"})]
    main = by_names[frozenset({"toy.main_thread", "toy_user_event"})]
    # attrs the span was opened with, scalars only; later ones stay on
    # the bus
    assert worker["toy.outer"][2] == {"rows": 3, "model": "m"}
    o, i = worker["toy.outer"], worker["toy.inner"]
    assert o[0] <= i[0] and i[1] <= o[1] and i[1] - i[0] >= 4_000_000
    assert main["toy.main_thread"][1] <= main["toy_user_event"][0]
    # the bus event keeps everything, on the bus clock
    ev = [e for e in telemetry.trace_events() if e["name"] == "toy.outer"][0]
    assert ev["args"]["late"] == 5 and ev["args"]["rows"] == 3


# ------------------------------------------------------- CPU time on a span
def _burn(seconds):
    t = time.thread_time()
    while time.thread_time() - t < seconds:
        sum(range(1000))


@pytest.mark.parametrize("cpu,carries", [
    (False, set()), (True, {"cpu_ms"}),
    ("process", {"cpu_ms", "proc_cpu_ms"})])
def test_span_carries_cpu_time_only_where_asked(cpu, carries):
    telemetry.enable()
    with telemetry.span("t.cpu", cpu=cpu, rows=3):
        _burn(0.02)
        time.sleep(0.03)
    (ev,) = [e for e in bus.events() if e[1] == "t.cpu"]
    attrs = ev[6]
    assert attrs["rows"] == 3 and "cpu" not in attrs
    assert {k for k in attrs if k.endswith("cpu_ms")} == carries
    if carries:
        # burned 20 ms, slept 30: CPU time is under the wall time
        assert 15.0 <= attrs["cpu_ms"] <= ev[4] / 1e3 - 20.0
    if "proc_cpu_ms" in carries:
        assert attrs["proc_cpu_ms"] >= attrs["cpu_ms"] - 1.0


def test_cpu_clock_reads_lie_outside_the_spans_own_duration(monkeypatch):
    """A CPU clock is a system call (6 us on the chip's host): it is read
    before the span opens and after it has closed, so a span that asks is
    no longer than one that does not, and the metrics that read its
    duration see no read.  Here a read takes 20 ms."""
    real = time.thread_time

    def slow():
        time.sleep(0.02)
        return real()

    monkeypatch.setattr(time, "thread_time", slow)
    monkeypatch.setattr(time, "process_time", slow)
    telemetry.enable()
    with telemetry.span("t.outer"):
        with telemetry.span("t.cpu", cpu="process"):
            pass
    monkeypatch.undo()
    dur = {e[1]: e[4] / 1e3 for e in bus.events() if e[1].startswith("t.")}
    assert dur["t.cpu"] < 10.0 and dur["t.outer"] >= 80.0


def test_bus_off_reads_no_cpu_clock(monkeypatch):
    """With the bus off a span that asks for CPU time is the shared no-op
    before any clock is read, and neither the decode step nor a request
    through the door reads one: both clocks are patched to raise."""
    import urllib.request
    from mxnet_tpu.serving.decode import DecodeRuntime, DecodeSession, \
        get_decode_model
    from mxnet_tpu.serving.gateway import Gateway
    from mxnet_tpu.telemetry import http as thttp

    net = get_decode_model("decode_tiny", vocab_size=61, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(1,), seq_buckets=(8,),
                       page_size=8)
    width = rt.cache.table_width
    args = (np.zeros((1,), "int32"), np.zeros((1,), "int32"),
            np.zeros((1, width), "int32"), np.zeros((1, 2), "uint32"),
            np.zeros((1,), "int32"), np.zeros((1,), "float32"))
    rt.step(*args)                                  # compiles, clocks live

    def refuse(*_a):
        raise AssertionError("a CPU clock was read with the bus off")

    assert not bus.enabled
    sess = DecodeSession(net, page_size=8, batch_buckets=(1,),
                         seq_buckets=(8,))
    gw = Gateway(port=0)
    gw.add_decode("m", sess)
    try:
        monkeypatch.setattr(time, "thread_time", refuse)
        monkeypatch.setattr(time, "process_time", refuse)
        assert telemetry.span("t.cpu", cpu="process") is bus._NOOP
        assert rt.step(*args).shape == (1,)
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.port}/v1/generate",
            data=json.dumps({"model": "m", "prompt": [5, 9, 2],
                              "max_new_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert len(json.loads(r.read())["token_ids"]) == 3
    finally:
        monkeypatch.undo()
        gw.close()
        sess.close()
        thttp.stop_server()
