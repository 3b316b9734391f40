"""PR 15 observability: request/step trace contexts and the merged
multi-host chrome trace, the always-on flight recorder, histogram metrics,
and the live /metrics + /healthz + /trace HTTP endpoint.

The tentpole contract test is the decode request lane: one request
submitted into a continuous batch must carry ONE trace id from
``submit()`` through queue wait, prefill, every step it rode, and its
eviction — across the client thread and the scheduler worker — and the
two-simulated-host drill must merge both hosts' streams into one timeline
with per-host lanes.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio, telemetry
from mxnet_tpu.analysis import sanitizer
from mxnet_tpu.serving.decode import DecodeRuntime, DecodeScheduler, \
    get_decode_model
from mxnet_tpu.telemetry import bus, exporters, flight, http, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "trace_host_worker.py")
VOCAB = 61


@pytest.fixture(autouse=True)
def _clean_stack():
    def _reset():
        telemetry.disable()
        telemetry.reset()
        trace.disarm()
        http.stop_server()
        flight.configure(capacity=flight.DEFAULT_CAPACITY, on=True)
        flight.reset()
        # the violation ``test_sanitizer_violation_auto_dumps`` provokes is
        # counted for the process: left behind, whichever file the worker
        # runs next that asserts a count of 0 fails
        # (``test_decode_pipeline.py``, when xdist hands both to one worker)
        sanitizer.reset()
    _reset()
    yield
    _reset()


def _spans(name=None):
    evs = [e for e in bus.events() if e[0] == "X"]
    return [e for e in evs if e[1] == name] if name else evs


def _attrs(ev):
    return ev[6] or {}


# ------------------------------------------------------------- histograms
class TestHistograms:
    def test_observe_counts_and_bounds(self):
        telemetry.enable()
        for v in (0.5, 3.0, 3.0, 40.0):
            telemetry.observe("t.lat_ms", v)
        h = telemetry.snapshot()["histograms"]["t.lat_ms"]
        assert h["count"] == 4
        assert h["sum"] == pytest.approx(46.5)
        assert h["min"] == 0.5 and h["max"] == 40.0
        assert h["min"] <= h["p50"] <= h["p90"] <= h["p99"] <= h["max"]

    def test_cumulative_buckets_end_at_inf(self):
        telemetry.enable()
        for v in range(1, 9):
            telemetry.observe("t.h", float(v))
        rows = telemetry.histograms()["t.h"]["buckets"]
        assert rows[-1] == ("+Inf", 8)
        cums = [c for _le, c in rows]
        assert cums == sorted(cums), "bucket counts must be cumulative"

    def test_quantile_interpolates_inside_bucket(self):
        telemetry.enable()
        for _ in range(10):
            telemetry.observe("t.q", 3.0)       # lands in the (2, 4] bucket
        q = telemetry.histogram_quantile("t.q", 0.5)
        assert 2.0 <= q <= 4.0
        assert telemetry.histogram_quantile("t.missing", 0.5) is None

    def test_prometheus_bucket_series(self):
        telemetry.enable()
        telemetry.observe("decode.ttft_ms", 12.5)
        text = exporters.dump_metrics()
        assert 'mxnet_decode_ttft_ms_bucket{le="16.0"} 1' in text
        assert 'mxnet_decode_ttft_ms_bucket{le="+Inf"} 1' in text
        assert "mxnet_decode_ttft_ms_sum 12.5" in text
        assert "mxnet_decode_ttft_ms_count 1" in text

    def test_disabled_is_noop(self):
        telemetry.observe("t.off", 1.0)
        assert telemetry.histograms() == {}


# ---------------------------------------------------------- trace contexts
class TestTraceContext:
    def test_nested_spans_chain_parent_ids(self):
        telemetry.enable()
        ctx = trace.start("t.root", who="test")
        with trace.use(ctx):
            with telemetry.span("t.outer"):
                with telemetry.span("t.inner"):
                    pass
        outer, inner = _spans("t.outer")[0], _spans("t.inner")[0]
        assert _attrs(outer)["trace_id"] == ctx.trace_id
        assert _attrs(inner)["trace_id"] == ctx.trace_id
        # root context: span_id == trace_id, so outer hangs off the root
        assert _attrs(outer)["parent_id"] == ctx.trace_id
        assert _attrs(inner)["parent_id"] == _attrs(outer)["span_id"]
        # the birth instant carries the root ids
        root = [e for e in bus.events() if e[0] == "I"
                and e[1] == "t.root"][0]
        assert _attrs(root)["span_id"] == ctx.trace_id

    def test_use_none_is_noop_and_stack_restores(self):
        telemetry.enable()
        with trace.use(None):
            assert trace.current() is None
        ctx = trace.start()
        with trace.use(ctx):
            assert trace.current().trace_id == ctx.trace_id
        assert trace.current() is None

    def test_record_span_on_explicit_lane(self):
        telemetry.enable()
        ctx = trace.start()
        t0 = time.perf_counter()
        telemetry.record_span("t.ride", t0, t0 + 0.001,
                              tid=ctx.trace_id, trace=ctx, hop=1)
        ev = _spans("t.ride")[0]
        assert ev[5] == ctx.trace_id, "tid must be the request lane"
        assert _attrs(ev)["parent_id"] == ctx.span_id
        assert _attrs(ev)["hop"] == 1

    def test_child_links_cross_thread_work(self):
        telemetry.enable()
        ctx = trace.start()
        link = trace.child(ctx)
        assert link[0] == ctx.trace_id and link[2] == ctx.span_id
        out = []

        def worker():
            t0 = time.perf_counter()
            telemetry.record_span("t.remote", t0, trace=link)
            out.append(True)

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        assert out
        ev = _spans("t.remote")[0]
        assert _attrs(ev)["span_id"] == link[1]
        assert _attrs(ev)["parent_id"] == ctx.span_id


# ------------------------------------------------------------ chrome merge
class TestChromeTrace:
    def test_flow_links_and_lane_metadata(self):
        telemetry.enable()
        ctx = trace.start("t.req")
        with trace.use(ctx):
            with telemetry.span("t.work"):
                pass
        doc = trace.chrome_trace()
        evs = doc["traceEvents"]
        assert any(e.get("ph") == "M" and e["name"] == "process_name"
                   for e in evs)
        starts = [e for e in evs if e.get("ph") == "s"]
        ends = [e for e in evs if e.get("ph") == "f"]
        assert starts and ends
        assert {e["id"] for e in starts} == {e["id"] for e in ends}

    def test_two_host_streams_merge_into_one_timeline(self, tmp_path):
        d = str(tmp_path)
        telemetry.enable()
        for host in (0, 1):
            trace.configure(d, host=host, host_count=2)
            ctx = trace.start(f"t.host{host}")
            with trace.use(ctx):
                with telemetry.span("t.step", host=host):
                    pass
            trace.disarm()
            telemetry.reset()      # the stream file, not the ring, is read
        doc = trace.chrome_trace(directory=d)
        evs = doc["traceEvents"]
        lanes = {e["pid"] for e in evs
                 if e.get("ph") == "X" and e["name"] == "t.step"}
        assert lanes == {0, 1}, "one process lane per simulated host"
        names = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert {"host 0", "host 1"} <= names

    def test_host_seed_prevents_id_collisions(self, tmp_path):
        telemetry.enable()
        trace.configure(str(tmp_path), host=0, host_count=2)
        a = bus.new_id()
        trace.configure(str(tmp_path), host=1, host_count=2)
        b = bus.new_id()
        assert (a >> 48) != (b >> 48)


# --------------------------------------------------------- flight recorder
class TestFlight:
    def test_ring_wraps_keeping_newest(self):
        flight.configure(capacity=16)
        for i in range(40):
            flight.record("f.ev", value=i)
        evs = flight.events()
        assert len(evs) == 16
        assert [e[3] for e in evs] == list(range(24, 40))

    def test_disabled_records_nothing(self):
        flight.configure(on=False)
        flight.record("f.off")
        assert flight.events() == []
        flight.configure(on=True)

    def test_dump_document(self, tmp_path):
        telemetry.enable()
        telemetry.count("t.counter", 3)
        telemetry.observe("t.lat_ms", 8.0)
        flight.record("f.step", detail="d", value=7)
        sp = telemetry.span("t.open")
        sp.__enter__()
        try:
            path = flight.dump("test-reason", path=str(tmp_path / "f.json"),
                               error=ValueError("boom"))
        finally:
            sp.__exit__(None, None, None)
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "test-reason"
        assert "boom" in doc["error"]
        assert any(e["name"] == "f.step" and e["value"] == 7
                   for e in doc["events"])
        assert any(s["name"] == "t.open" for s in doc["active_spans"])
        assert doc["telemetry"]["counters"]["t.counter"] == 3
        assert "t.lat_ms" in doc["telemetry"]["histograms"]

    def test_postmortem_without_dir_is_silent(self, monkeypatch):
        monkeypatch.delenv("MXNET_FLIGHT_DIR", raising=False)
        flight.record("f.pre")
        assert flight.postmortem("no-dir") is None
        assert any(e[1] == "flight.postmortem" for e in flight.events())

    def test_sanitizer_violation_auto_dumps(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))

        class FakeCache:
            def generation(self, slot_id):
                return 7

        cache = FakeCache()
        with sanitizer.scope("slots"):
            sanitizer.register_kv_slot(cache, 3, "test.site")
            flight.record("decode.step", value=1)
            # clean check: no dump
            sanitizer.check_kv_slot(cache, 3, generation=7)
            assert not os.listdir(str(tmp_path))
            with pytest.raises(sanitizer.StaleKVSlotError):
                sanitizer.check_kv_slot(cache, 3, generation=5)
        dumps = [f for f in os.listdir(str(tmp_path))
                 if f.startswith("flight-")]
        assert len(dumps) == 1, "violation must leave exactly one dump"
        with open(tmp_path / dumps[0]) as f:
            doc = json.load(f)
        assert doc["reason"] == "StaleKVSlotError"
        names = [e["name"] for e in doc["events"]]
        assert "decode.step" in names, "ring history precedes the fault"
        assert "sanitizer.violation" in names


# ------------------------------------------------------- decode request lane
@pytest.fixture(scope="module")
def runtime():
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(1, 2), seq_buckets=(8, 16),
                       page_size=8)
    yield rt


def _lane_events(lane):
    return [e for e in bus.events() if e[5] == lane]


class TestDecodeRequestLane:
    def test_solo_request_one_trace_submit_to_eviction(self, runtime):
        telemetry.enable()
        sched = DecodeScheduler(runtime)
        try:
            fut = sched.submit([5, 9, 2], max_new_tokens=4)
            res = fut.result(timeout=120)
        finally:
            sched.close(drain=True, timeout=30.0)
        assert len(res.token_ids) >= 1
        roots = [e for e in bus.events()
                 if e[0] == "I" and e[1] == "decode.submit"]
        assert len(roots) == 1
        lane = _attrs(roots[0])["trace_id"]
        names = [e[1] for e in _lane_events(lane)]
        for hop in ("decode.queue_wait", "decode.ride_prefill",
                    "decode.ride_step", "decode.evict"):
            assert hop in names, f"lane missing {hop}: {names}"
        assert names.count("decode.ride_step") >= 1
        # one trace id across every hop, each hop linked into the tree
        for ev in _lane_events(lane):
            assert _attrs(ev)["trace_id"] == lane
            assert "parent_id" in _attrs(ev) or "span_id" in _attrs(ev)
        evict = [e for e in _lane_events(lane) if e[1] == "decode.evict"][0]
        assert _attrs(evict)["parent_id"] == lane, \
            "eviction must link to the submit root"

    def test_continuous_batch_keeps_per_request_trace(self, runtime):
        telemetry.enable()
        sched = DecodeScheduler(runtime)
        try:
            first = sched.submit([3, 7, 1], max_new_tokens=24)
            # wait until the first request is actually riding steps, so the
            # second genuinely joins a running batch mid-flight
            deadline = time.perf_counter() + 60
            while not _spans("decode.ride_step") and \
                    time.perf_counter() < deadline:
                time.sleep(0.001)
            second = sched.submit([8, 4], max_new_tokens=4)
            r1, r2 = first.result(timeout=120), second.result(timeout=120)
        finally:
            sched.close(drain=True, timeout=30.0)
        assert len(r1.token_ids) >= 1 and len(r2.token_ids) >= 1
        roots = [e for e in bus.events()
                 if e[0] == "I" and e[1] == "decode.submit"]
        assert len(roots) == 2
        lanes = [_attrs(r)["trace_id"] for r in roots]
        assert lanes[0] != lanes[1]
        for lane in lanes:
            names = [e[1] for e in _lane_events(lane)]
            for hop in ("decode.queue_wait", "decode.ride_prefill",
                        "decode.ride_step", "decode.evict"):
                assert hop in names, f"lane {lane:#x} missing {hop}"
            ids = {_attrs(e)["trace_id"] for e in _lane_events(lane)}
            assert ids == {lane}, "a lane must carry exactly one trace"
        # shared steps: some ride_step spans saw batch > 1 (a mid-flight
        # join), and the hop is billed to BOTH requests' lanes
        snap = telemetry.snapshot()
        assert snap["counters"].get("decode.joins", 0) >= 1, \
            "second request never joined the running batch"
        rides = [e for e in _spans("decode.ride_step")]
        assert any(_attrs(e).get("batch", 1) > 1 for e in rides), \
            "shared steps must bill batch>1 rides to both lanes"
        hist = snap["histograms"]
        assert hist["decode.ttft_ms"]["count"] == 2
        assert hist["decode.step_ms"]["count"] >= 1


# ------------------------------------------------- scheduler phase spans
def _tree(events):
    """``{span_id: event}`` and ``{parent_id: [children]}`` of bus spans."""
    by_id = {_attrs(e)["span_id"]: e for e in events if "span_id" in _attrs(e)}
    kids = {}
    for e in by_id.values():
        kids.setdefault(_attrs(e).get("parent_id"), []).append(e)
    return by_id, kids


class TestSchedulerPhases:
    PHASES = {"decode.admit", "decode.prefill.prepare", "decode.prefill",
              "decode.prefill.fanout", "decode.step.prepare", "decode.step",
              "decode.step.fanout"}

    @pytest.fixture(scope="class")
    def served(self, runtime):
        """Two requests through a live scheduler, the second joining the
        first mid-flight, served once for the class (a repeated prompt
        would hit the shared-prefix index and skip its prefill).  Returns
        the worker thread's spans, the histograms and the exposition."""
        telemetry.reset()
        telemetry.enable()
        sched = DecodeScheduler(runtime)
        try:
            deadline = time.perf_counter() + 60
            while not any(n == "decode.idle" for n, _t, _i in
                          bus.open_spans()) and \
                    time.perf_counter() < deadline:
                time.sleep(0.001)
            a = sched.submit([11, 23, 35, 47, 59], max_new_tokens=12)
            while not _spans("decode.ride_step") and \
                    time.perf_counter() < deadline:
                time.sleep(0.001)
            b = sched.submit([58, 46, 34], max_new_tokens=3)
            a.result(timeout=120), b.result(timeout=120)
            tid = sched._worker.ident
        finally:
            sched.close(drain=True, timeout=30.0)
        telemetry.gauge("t.depth", 1, model="a")
        telemetry.gauge("t.depth", 2, model="b")
        out = {"spans": [e for e in _spans() if e[5] == tid],
               "histograms": telemetry.histograms(),
               "metrics": telemetry.dump_metrics()}
        telemetry.disable()
        telemetry.reset()
        return out

    def test_turn_phases_nest_and_self_times_add_up(self, served):
        served = served["spans"]
        by_id, kids = _tree(served)
        boundaries = [e for e in served if e[1] == "decode.boundary"]
        assert len(boundaries) >= 8
        seen = set()
        for b in boundaries:
            # a turn is one trace: its root is the boundary's parent
            assert _attrs(b)["parent_id"] == _attrs(b)["trace_id"]

            def self_us(e):
                """Self time of e plus of everything under it, checking
                that children lie inside e and do not overlap."""
                mine = sorted(kids.get(_attrs(e)["span_id"], []),
                              key=lambda c: c[3])
                end = e[3]
                for c in mine:
                    assert c[3] >= end - 1e-3 and \
                        c[3] + c[4] <= e[3] + e[4] + 1e-3, (e[1], c[1])
                    end = c[3] + c[4]
                own = e[4] - sum(c[4] for c in mine)
                assert own >= -1e-3, (e[1], own)
                return own + sum(self_us(c) for c in mine)

            names = {c[1] for c in kids.get(_attrs(b)["span_id"], [])}
            assert "decode.admit" in names and names <= self.PHASES, names
            seen |= names
            assert self_us(b) == pytest.approx(b[4], rel=1e-9, abs=1e-3)
        assert seen == self.PHASES
        joins = [b for b in boundaries if _attrs(b)["joining"]]
        assert len(joins) >= 2 and any(_attrs(b)["active"] for b in joins)
        admits = [e for e in served if e[1] == "decode.admit"]
        assert sum(_attrs(e)["admitted"] for e in admits) == 2

    def test_idle_wait_is_a_span_of_its_own_turn(self, served):
        served = served["spans"]
        idles = [e for e in served if e[1] == "decode.idle"]
        assert idles, "the worker waited for the first request"
        by_id, kids = _tree(served)
        for e in idles:
            # root of the turn is the parent; the boundary that follows the
            # wait belongs to the same turn
            turn = _attrs(e)["trace_id"]
            assert _attrs(e)["parent_id"] == turn
            after = [b for b in kids.get(turn, [])
                     if b[1] == "decode.boundary"]
            assert all(b[3] >= e[3] + e[4] - 1e-3 for b in after)

    @pytest.mark.parametrize("call", ["decode.step", "decode.prefill"])
    def test_runtime_span_ends_after_its_fetch(self, served, call):
        """The runtime's span is the whole call: dispatch, then the fetch
        of the sampled tokens, both inside it.  For ``decode.step`` it is
        the runtime's call of one TURN (ISSUE 36): in a pipelined turn the
        launch of the next step, then the collect of the one before it; in
        a synchronous turn one of the two alone."""
        hists, served = served["histograms"], served["spans"]
        by_id, kids = _tree(served)
        calls = [e for e in served if e[1] == call and "batch" in _attrs(e)]
        assert calls
        both = {call + ".dispatch", call + ".fetch"}
        whole = []
        for e in calls:
            parts = {c[1]: c for c in kids[_attrs(e)["span_id"]]}
            if call == "decode.step" and len(parts) == 1:
                (c,) = parts.values()
                assert c[1] in both
                assert e[3] <= c[3] and c[3] + c[4] <= e[3] + e[4] + 1e-3
                whole += [e] if c[1].endswith(".fetch") else []
                continue
            assert set(parts) == both
            d, f = parts[call + ".dispatch"], parts[call + ".fetch"]
            assert e[3] <= d[3] and d[3] + d[4] <= f[3] + 1e-3
            assert f[3] + f[4] <= e[3] + e[4] + 1e-3
            assert e[4] >= d[4] + f[4] - 1e-3
            whole.append(e)
        # the scheduler's own bracket of every call that collected a step
        # holds the span; most turns launched ahead of their collect
        if call == "decode.step":
            steps = hists["decode.step_ms"]
            assert steps["count"] == len(whole)
            assert steps["sum"] >= sum(e[4] for e in whole) / 1e3 - 1e-3
            assert sum(len(kids[_attrs(e)["span_id"]]) == 2
                       for e in calls) >= len(calls) // 2

    TURN = {"decode.admit", "decode.prefill.prepare",
            "decode.prefill.fanout", "decode.step.prepare",
            "decode.step.fanout", "decode.prefill.dispatch",
            "decode.prefill.fetch", "decode.step.dispatch",
            "decode.step.fetch", "decode.boundary"}
    # a CPU clock is a system call: only the two spans that a metric reads
    # it from ask
    CPU = {"decode.boundary", "decode.step.dispatch"}

    @pytest.mark.parametrize("name", sorted(TURN))
    def test_the_loop_accounts_for_its_cpu(self, served, name):
        """The turn carries the thread's CPU time and the process's, which
        holds the thread's; the step's launch carries the thread's (never
        more than its wall time, beyond the clocks' grain); no other phase
        of a turn reads a CPU clock."""
        found = [e for e in served["spans"] if e[1] == name]
        assert found
        for e in found:
            a = _attrs(e)
            assert ("cpu_ms" in a) == (name in self.CPU)
            assert ("proc_cpu_ms" in a) == (name == "decode.boundary")
            if name in self.CPU:
                assert 0.0 <= a["cpu_ms"] <= e[4] / 1e3 + 1.0, (a, e[4])
            if name == "decode.boundary":
                assert a["proc_cpu_ms"] >= a["cpu_ms"] - 1.0
            assert "leaves" not in a and "host_args" not in a

    def test_metrics_exposition_has_one_type_per_family(self, served):
        text = served["metrics"]
        typed = [ln.split()[2] for ln in text.splitlines()
                 if ln.startswith("# TYPE ")]
        assert len(typed) == len(set(typed)), sorted(
            t for t in set(typed) if typed.count(t) > 1)
        assert "# TYPE mxnet_decode_ttft_ms histogram" in text
        assert "mxnet_decode_ttft_ms_count 2" in text
        assert "mxnet_decode_ttft_calls" not in text     # the span is gone
        assert 'mxnet_t_depth{model="b"} 2' in text


# --------------------------------------------------------- io worker lanes
N_IMG, HW = 32, 48


@pytest.fixture(scope="module")
def rec_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tracerec") / "data.rec")
    rng = np.random.RandomState(0)
    rec = recordio.MXRecordIO(path, "w")
    img = (rng.rand(HW, HW, 3) * 255).astype("uint8")
    for i in range(N_IMG):
        img[i % HW, :, :] = (i * 37) % 255
        header = recordio.IRHeader(0, float(i % 10), i, 0)
        rec.write(recordio.pack_img(header, img, quality=85))
    rec.close()
    return path


class TestIOWorkerLanes:
    def test_worker_decode_spans_parent_to_consumer_batch(self, rec_path):
        telemetry.enable()
        it = mx.io.ImageRecordIter(path_imgrec=rec_path,
                                   data_shape=(3, 32, 32), batch_size=16,
                                   preprocess_processes=2)
        n = sum(1 for _ in it)
        it.close()
        assert n >= 2
        waits = _spans("io.proc_batch_wait")
        decodes = _spans("io.worker_decode")
        assert waits and decodes, "worker decode spans must cross the shm ring"
        wait_by_seq = {_attrs(e)["seq"]: e for e in waits}
        for ev in decodes:
            a = _attrs(ev)
            # the worker's span rides a per-worker process-style lane...
            assert ev[5] == 0xD0000 + a["worker"]
            # ...and parents to the consumer-side wait for the SAME batch
            parent = wait_by_seq[a["seq"]]
            assert a["parent_id"] == _attrs(parent)["span_id"]
            assert a["trace_id"] == _attrs(parent)["trace_id"]
            assert ev[4] > 0, "worker decode must have real duration"


# ------------------------------------------------------------ http endpoint
def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class _Probe:
    def __init__(self, healthy=True):
        self.healthy = healthy


class TestHttpEndpoint:
    def test_metrics_healthz_trace_routes(self):
        telemetry.enable()
        telemetry.count("t.reqs", 2)
        telemetry.observe("t.lat_ms", 5.0)
        port = http.start_server(0)
        assert http.server_port() == port

        code, body = _get(port, "/metrics")
        assert code == 200
        assert "mxnet_t_reqs 2" in body
        assert 'mxnet_t_lat_ms_bucket{le="+Inf"} 1' in body

        code, body = _get(port, "/healthz")
        assert code == 200 and json.loads(body)["ok"] is True

        code, body = _get(port, "/trace")
        assert code == 200
        assert "traceEvents" in json.loads(body)

        code, _body = _get(port, "/nope")
        assert code == 404

    def test_healthz_flips_with_probe(self):
        port = http.start_server(0)
        probe = _Probe(healthy=True)
        http.register_health("t:probe", probe)
        try:
            assert _get(port, "/healthz")[0] == 200
            probe.healthy = False
            code, body = _get(port, "/healthz")
            assert code == 503
            assert json.loads(body)["components"]["t:probe"] is False
        finally:
            http.unregister_health("t:probe")
        assert _get(port, "/healthz")[0] == 200

    def test_batcher_registers_and_unregisters(self):
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        rt = mx.serving.ModelRuntime(net, item_shapes=(8,), max_batch=2)
        b = mx.serving.Batcher(rt, start=False)
        try:
            # batchers report *readiness* (route away), not liveness
            ok, report = http.readiness()
            assert report.get(f"batcher:{rt.name}") is True and ok
            _ok, live = http.health()
            assert f"batcher:{rt.name}" not in live
        finally:
            b.close(drain=False)
        _ok, report = http.readiness()
        assert f"batcher:{rt.name}" not in report

    def test_shutdown_ordering_is_bounded(self):
        telemetry.enable()
        telemetry.start_counter_sampler(["t.reqs"], interval_ms=10)
        port = http.start_server(0)
        assert _get(port, "/metrics")[0] == 200
        t0 = time.perf_counter()
        http.stop_server()
        telemetry.stop_counter_sampler()
        assert time.perf_counter() - t0 < 5.0
        assert http.server_port() is None
        assert not telemetry.sampler_running()


# ----------------------------------------------------- two-host trace drill
def _spawn(dirpath, host, extra=()):
    env = dict(os.environ, PYTHONPATH=REPO)
    for k in ("MXNET_SANITIZE", "MXNET_CKPT_HOST", "MXNET_TELEMETRY",
              "MXNET_TRACE_DIR", "MXNET_FLIGHT_DIR"):
        env.pop(k, None)
    return subprocess.Popen(
        [sys.executable, WORKER, "--dir", dirpath, "--host", host,
         "--steps", "3", "--timeout", "60", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)


def _flight_dumps(d):
    return sorted(f for f in os.listdir(d) if f.startswith("flight-"))


class TestTwoHostDrill:
    def test_clean_run_merges_one_timeline_no_dump(self, tmp_path):
        d = str(tmp_path)
        procs = [_spawn(d, "0/2"), _spawn(d, "1/2")]
        outs = [p.communicate(timeout=300)[0] for p in procs]
        assert [p.returncode for p in procs] == [0, 0], outs
        assert os.path.exists(os.path.join(d, "trace-0.jsonl")), outs
        assert os.path.exists(os.path.join(d, "trace-1.jsonl")), outs
        # a third process — the "driver" — merges the two streams
        doc = trace.chrome_trace(path=os.path.join(d, "merged.json"),
                                 directory=d)
        with open(os.path.join(d, "merged.json")) as f:
            reparsed = json.load(f)           # valid JSON on disk
        assert reparsed["traceEvents"]
        steps = [e for e in doc["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "trainer.step"]
        lanes = {e["pid"] for e in steps}
        assert lanes == {0, 1}, "both hosts' step spans in one timeline"
        for e in steps:
            assert "trace_id" in e["args"], "steps must carry trace roots"
        # clean run: the flight recorder stays silent
        assert _flight_dumps(d) == [], outs

    def test_planted_divergence_dumps_both_hosts(self, tmp_path):
        d = str(tmp_path)
        procs = [_spawn(d, "0/2"),
                 _spawn(d, "1/2", extra=("--diverge-at", "2"))]
        outs = [p.communicate(timeout=300)[0] for p in procs]
        assert [p.returncode for p in procs] == [3, 3], outs
        dumps = _flight_dumps(d)
        hosts = set()
        for name in dumps:
            with open(os.path.join(d, name)) as f:
                doc = json.load(f)
            assert doc["reason"] == "CollectiveDivergenceError", doc["reason"]
            assert "CollectiveDivergenceError" in doc["error"]
            hosts.add(doc["host"])
            names = [e["name"] for e in doc["events"]]
            assert "trainer.step" in names, \
                "dump must show the host's last framework beats"
            assert "collective" in names, \
                "dump must show the fingerprints leading up to the fault"
            assert "sanitizer.violation" in names
        assert hosts == {0, 1}, (dumps, outs)
