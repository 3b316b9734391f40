"""serving.gateway: the HTTP front door (ISSUE 18 tentpole).

Covers route behaviour end-to-end over a real localhost socket: buffered
vs SSE-streamed ``/v1/generate`` (bitwise-identical tokens), ``/v1/infer``
through a ModelRegistry, QoS admission sheds as 429-with-Retry-After,
error→status mapping, /healthz + /metrics on the same port, and the
satellite: an atomic registry hot-swap under live concurrent HTTP
traffic with zero dropped or torn responses.
"""
import http.client
import json
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serving import Batcher, ModelRegistry, ModelRuntime
from mxnet_tpu.serving.decode import DecodeSession, get_decode_model
from mxnet_tpu.serving.gateway import AdmissionController, Gateway
from mxnet_tpu.telemetry import http as thttp

ITEM = (24,)
VOCAB = 96


@pytest.fixture(autouse=True)
def _clean():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()
    thttp.stop_server()


@pytest.fixture(scope="module")
def decode_sess():
    mx.random.seed(0)
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    sess = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8,),
                         page_size=8)
    yield sess
    sess.close(drain=False)


def _make_net(const=None):
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"))
        net.add(mx.gluon.nn.Dense(4))
    net.initialize(mx.init.Constant(const) if const is not None else None)
    return net


def _post(port, path, body, timeout=60):
    """POST json, return (status, headers-dict, raw-bytes).  Streaming
    responses close the connection, so read() drains to EOF."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _sse_frames(raw):
    """Parse an SSE body into the list of ``data:`` payload strings."""
    out = []
    for chunk in raw.decode().split("\n\n"):
        chunk = chunk.strip()
        if chunk.startswith("data: "):
            out.append(chunk[len("data: "):])
    return out


# ---------------------------------------------------------------- admission
def test_admission_guaranteed_share_and_borrowing():
    ac = AdmissionController(capacity=4)
    ac.set_weight("a", 3.0)
    ac.set_weight("b", 1.0)
    # a's guaranteed share is 3, b's is 1
    assert all(ac.try_acquire("a") for _ in range(3))
    assert ac.try_acquire("b")
    # capacity reached and both are at/over share -> shed
    assert not ac.try_acquire("b")
    assert ac.shed == 1
    # idle capacity is borrowable once someone releases
    ac.release("a")
    assert ac.try_acquire("b")          # borrows a's idle share
    assert ac.borrowed >= 1
    snap = ac.snapshot()
    assert snap["inflight"] == {"a": 2, "b": 2}
    with pytest.raises(ValueError):
        ac.set_weight("a", 0)
    with pytest.raises(ValueError):
        AdmissionController(capacity=0)


def test_admission_floored_share_always_admits_one():
    ac = AdmissionController(capacity=2)
    ac.set_weight("big", 100.0)
    assert ac.try_acquire("big")
    assert ac.try_acquire("big")
    # tiny's proportional share rounds to 0 but floors at 1 — the
    # bounded-overshoot contract: a guarantee, not a hint
    assert ac.try_acquire("tiny")
    assert ac.inflight() == 3


# ------------------------------------------------------------- /v1/generate
def test_generate_buffered_vs_streamed_bitwise(decode_sess):
    with Gateway() as gw:
        gw.add_decode("tiny", decode_sess)
        req = {"model": "tiny", "prompt": [5, 9, 2],
               "max_new_tokens": 8, "temperature": 0.8, "seed": 11}
        st, _, raw = _post(gw.port, "/v1/generate", req)
        assert st == 200
        buffered = json.loads(raw)
        assert buffered["model"] == "tiny"
        assert len(buffered["token_ids"]) == 8
        assert buffered["finish_reason"] == "length"

        st, hdr, raw = _post(gw.port, "/v1/generate",
                             dict(req, stream=True))
        assert st == 200
        assert hdr.get("Content-Type") == "text/event-stream"
        frames = _sse_frames(raw)
        assert frames[-1] == "[DONE]"
        toks = [json.loads(f) for f in frames[:-1]]
        done = toks.pop()
        assert done["done"] is True and done["n_tokens"] == 8
        assert done["finish_reason"] == "length"
        assert [t["index"] for t in toks] == list(range(8))
        # the bitwise contract: SSE carries exactly the buffered sequence
        assert [t["token"] for t in toks] == buffered["token_ids"]


def test_streamed_request_is_one_trace_from_socket_to_first_frame(
        decode_sess):
    """One trace id per request: the wire-side root, the scheduler's
    submit, queue wait and prefill ride, and the first frame's egress, each
    hop linked to the one before."""
    from mxnet_tpu.telemetry import bus
    telemetry.enable()
    with Gateway() as gw:
        gw.add_decode("tiny", decode_sess)
        st, _, raw = _post(gw.port, "/v1/generate",
                           {"model": "tiny", "prompt": [41, 7, 19, 33, 2, 50],
                            "max_new_tokens": 5, "stream": True})
        assert st == 200 and _sse_frames(raw)[-1] == "[DONE]"
    evs = bus.events()
    root = [e for e in evs if e[1] == "gateway.request"]
    assert len(root) == 1
    trace_id = root[0][6]["trace_id"]
    assert root[0][6]["span_id"] == trace_id
    lane = {e[1]: e for e in evs if e[1] != "decode.ride_step"
            and (e[6] or {}).get("trace_id") == trace_id}
    assert set(lane) == {"gateway.request", "decode.submit",
                         "decode.queue_wait", "decode.ride_prefill",
                         "gateway.first_frame", "decode.evict"}
    submit = lane["decode.submit"][6]
    assert submit["parent_id"] == trace_id
    for hop in ("decode.queue_wait", "decode.ride_prefill", "decode.evict"):
        assert lane[hop][6]["parent_id"] == submit["span_id"], hop
        assert lane[hop][5] == trace_id        # the request's own lane
    first = lane["gateway.first_frame"]
    assert first[6]["parent_id"] == trace_id and first[5] == trace_id
    # first token put (end of the prefill ride's fan-out) -> frame flushed
    ride = lane["decode.ride_prefill"]
    assert first[3] >= ride[3] + ride[4] - 1e-3 and 0 <= first[4] < 5e6
    rides = [e for e in evs if e[1] == "decode.ride_step"]
    assert rides and all(e[6]["trace_id"] == trace_id for e in rides)


def test_generate_default_model_and_errors(decode_sess):
    with Gateway() as gw:
        gw.add_decode("tiny", decode_sess)
        # sole registered model is the default
        st, _, raw = _post(gw.port, "/v1/generate",
                           {"prompt": [1, 2], "max_new_tokens": 2})
        assert st == 200 and json.loads(raw)["model"] == "tiny"
        st, _, raw = _post(gw.port, "/v1/generate",
                           {"model": "nope", "prompt": [1]})
        assert st == 404 and json.loads(raw)["error"] == "unknown_model"
        st, _, raw = _post(gw.port, "/v1/generate",
                           {"model": "tiny", "prompt": []})
        assert st == 400
        # malformed JSON body
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
        try:
            conn.request("POST", "/v1/generate", b"{nope",
                         {"Content-Type": "application/json"})
            assert conn.getresponse().status == 400
        finally:
            conn.close()


def test_generate_qos_shed_is_429_with_retry_after(decode_sess):
    with Gateway(capacity=1) as gw:
        gw.add_decode("tiny", decode_sess)
        assert gw.admission.try_acquire("tiny")   # hold the only slot
        try:
            telemetry.enable()
            st, hdr, raw = _post(gw.port, "/v1/generate",
                                 {"prompt": [3], "max_new_tokens": 1})
            assert st == 429
            assert float(hdr["Retry-After"]) > 0
            assert json.loads(raw)["error"] == "qos"
            by_label = telemetry.snapshot()["counters_by_label"]
            assert any('reason="qos"' in k
                       for k in by_label.get("gateway.shed", {}))
        finally:
            gw.admission.release("tiny")


def test_streamed_shed_maps_like_buffered(decode_sess):
    # a deadline that expires before admission -> 429, both paths
    with Gateway() as gw:
        gw.add_decode("tiny", decode_sess)
        req = {"prompt": [4, 4], "max_new_tokens": 4, "deadline_ms": 0.0}
        st, hdr, raw = _post(gw.port, "/v1/generate", req)
        assert st == 429 and json.loads(raw)["error"] == "deadline"
        assert "Retry-After" in hdr
        # streamed: shed surfaces as an in-stream error frame (headers
        # are already on the wire) and the stream still terminates
        st, _, raw = _post(gw.port, "/v1/generate",
                           dict(req, stream=True))
        frames = _sse_frames(raw)
        assert frames[-1] == "[DONE]"
        payloads = [json.loads(f) for f in frames[:-1]]
        assert payloads[-1].get("error") == "deadline"
        assert not any("token" in p for p in payloads)


# ---------------------------------------------------------------- /v1/infer
def test_infer_roundtrip_and_errors():
    reg = ModelRegistry()
    rt = ModelRuntime(_make_net(), ITEM, max_batch=4)
    reg.register("m", rt, max_latency_ms=2)
    try:
        with Gateway(registry=reg) as gw:
            x = np.random.RandomState(0).rand(*ITEM).astype("float32")
            st, _, raw = _post(gw.port, "/v1/infer",
                               {"model": "m", "inputs": x.tolist()})
            assert st == 200
            body = json.loads(raw)
            np.testing.assert_allclose(body["outputs"], rt(x),
                                       rtol=1e-5, atol=1e-6)
            st, _, _ = _post(gw.port, "/v1/infer",
                             {"model": "ghost", "inputs": [1.0]})
            assert st == 404
            st, _, raw = _post(gw.port, "/v1/infer", {"model": "m"})
            assert st == 400
            assert "inputs" in json.loads(raw)["detail"]
    finally:
        reg.close()


def test_infer_without_registry_is_404(decode_sess):
    with Gateway() as gw:
        st, _, raw = _post(gw.port, "/v1/infer",
                           {"model": "m", "inputs": [1.0]})
        assert st == 404


# ------------------------------------------------- hot swap under live fire
def test_registry_hot_swap_under_live_http_traffic():
    """ISSUE 18 satellite: swap a model's weights while HTTP clients
    hammer /v1/infer.  Every request must answer 200 with an output that
    is exactly the old or the new model's — zero drops, zero torn reads,
    and post-swap requests see the new weights."""
    reg = ModelRegistry()
    rt1 = ModelRuntime(_make_net(const=0.1), ITEM, max_batch=4, name="m")
    rt2 = ModelRuntime(_make_net(const=0.3), ITEM, max_batch=4, name="m")
    reg.register("m", rt1, max_latency_ms=1)
    x = np.random.RandomState(1).rand(*ITEM).astype("float32")
    ref1, ref2 = np.asarray(rt1(x)), np.asarray(rt2(x))
    assert not np.allclose(ref1, ref2)

    results = {}          # thread-name -> list of (status, outputs)
    errors = []
    n_threads, n_reqs = 4, 24
    body = {"model": "m", "inputs": x.tolist()}

    with Gateway(registry=reg, capacity=64) as gw:
        def client(tag):
            got = []
            try:
                for _ in range(n_reqs):
                    st, _, raw = _post(gw.port, "/v1/infer", body)
                    got.append((st, json.loads(raw).get("outputs")))
            except Exception as e:        # noqa: BLE001 — fail the test
                errors.append((tag, repr(e)))
            results[tag] = got

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        time.sleep(0.05)                    # traffic in flight
        reg.swap("m", rt2, max_latency_ms=1)
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors

        # zero dropped requests: every client got every answer
        assert all(len(results[i]) == n_reqs for i in range(n_threads))
        flat = [r for got in results.values() for r in got]
        assert all(st == 200 for st, _ in flat), \
            sorted({st for st, _ in flat})
        # zero torn responses: each output is exactly one model's answer
        n_new = 0
        for _, out in flat:
            is_old = np.allclose(out, ref1, rtol=1e-5, atol=1e-6)
            is_new = np.allclose(out, ref2, rtol=1e-5, atol=1e-6)
            assert is_old ^ is_new, out
            n_new += int(is_new)
        assert n_new > 0                    # the swap actually landed
        # and the steady state is the new weights
        st, _, raw = _post(gw.port, "/v1/infer", body)
        np.testing.assert_allclose(json.loads(raw)["outputs"], ref2,
                                   rtol=1e-5, atol=1e-6)
    reg.close()


# ---------------------------------------------------- shared-port telemetry
def test_healthz_metrics_and_routes_share_the_port(decode_sess):
    telemetry.enable()
    with Gateway() as gw:
        gw.add_decode("tiny", decode_sess, weight=2.0)
        st, raw = _get(gw.port, "/healthz")
        assert st == 200
        report = json.loads(raw)
        assert report["components"].get("gateway:gateway") is True
        _post(gw.port, "/v1/generate",
              {"prompt": [7], "max_new_tokens": 2})
        st, raw = _get(gw.port, "/metrics")
        assert st == 200
        text = raw.decode()
        assert "gateway_requests" in text or "gateway.requests" in text
        counters = telemetry.snapshot()["counters"]
        assert counters.get("gateway.requests") == 1
        assert counters.get("gateway.responses") == 1
        hists = telemetry.snapshot()["histograms"]
        assert "gateway.ttft_buffered_ms" in hists
        assert "gateway.queue_wait_ms" in hists
    # close() unmounted the routes: the port still answers, /v1 404s
    port = thttp.server_port()
    assert port is not None
    st, _, _ = _post(port, "/v1/generate", {"prompt": [1]})
    assert st == 404
    st, raw = _get(port, "/healthz")
    assert st == 200
    assert "gateway:gateway" not in json.loads(raw)["components"]


@pytest.mark.parametrize("stream", [False, True])
def test_handler_cpu_is_counted_beside_the_responses(decode_sess, stream,
                                                     monkeypatch):
    """What a request costs the interpreter on its handler thread, from the
    wire to the last flush: two reads of the thread's CPU clock a request,
    counted by route beside ``gateway.responses``, buffered or streamed.
    The clock is a scripted one that moves a millisecond a read, so the
    count is exact whatever the host's clock can resolve; the count lands
    behind the last flush, so the client waits for it."""
    reads = iter(range(10**6))
    monkeypatch.setattr(time, "thread_time", lambda: next(reads) * 1e-3)

    def counted(more_than):
        deadline = time.perf_counter() + 10
        while time.perf_counter() < deadline:
            snap = telemetry.snapshot()
            got = snap["counters"].get("gateway.handler_cpu_ms", 0.0)
            if got > more_than:
                return got, snap
            time.sleep(0.005)
        raise AssertionError("gateway.handler_cpu_ms did not grow")

    telemetry.enable()
    with Gateway() as gw:
        gw.add_decode("tiny", decode_sess)
        seen = 0.0
        for k in range(2):
            st, _, _ = _post(gw.port, "/v1/generate",
                             {"model": "tiny", "prompt": [5, 9, 2 + k],
                              "max_new_tokens": 6, "stream": stream})
            assert st == 200
            got, snap = counted(seen)
            # the handler's two reads, and whatever the scheduler's spans
            # read in between
            assert got - seen >= 1.0
            assert snap["counters"]["gateway.responses"] == k + 1
            assert snap["counters_by_label"]["gateway.handler_cpu_ms"] == {
                '{route="generate"}': got}
            seen = got
    telemetry.disable()
    with Gateway() as gw:           # off: nothing is counted, no clock read
        gw.add_decode("tiny", decode_sess)
        at = next(reads)
        st, _, _ = _post(gw.port, "/v1/generate",
                         {"model": "tiny", "prompt": [1],
                          "max_new_tokens": 2})
        assert st == 200 and next(reads) == at + 1
    assert telemetry.snapshot()["counters"]["gateway.handler_cpu_ms"] == seen


def test_unhealthy_gateway_flips_healthz(decode_sess):
    gw = Gateway()
    try:
        gw.add_decode("tiny", decode_sess)
        gw._closed = True                  # simulate a wedged front door
        st, raw = _get(gw.port, "/healthz")
        assert st == 503
        assert json.loads(raw)["components"]["gateway:gateway"] is False
        gw._closed = False
        st, _ = _get(gw.port, "/healthz")
        assert st == 200
    finally:
        gw._closed = False
        gw.close()


# ------------------------------------------- ISSUE 19: graceful degradation
def test_compute_retry_after_per_reason():
    """Every shed reason derives its Retry-After from the live state
    that caused it — not one constant that synchronizes retry storms."""
    ac = AdmissionController(capacity=10, retry_after_s=1.0)
    # breaker open: hint == the actual remaining cool-down
    assert ac.compute_retry_after("unhealthy",
                                  breaker_remaining_s=3.25) == 3.25
    assert ac.compute_retry_after("unhealthy",
                                  breaker_remaining_s=0.01) == 0.1
    assert ac.compute_retry_after("unhealthy") == 5.0     # no breaker info
    # shutdown: long — clients should fail over, not camp
    assert ac.compute_retry_after("shutdown") >= 10.0
    # owner crash: sized past an AOT-warm supervisor respawn
    assert ac.compute_retry_after("owner_unavailable") >= 2.0
    # qos: scales with gateway contention
    assert ac.compute_retry_after("qos", inflight=0) == 1.0
    assert ac.compute_retry_after("qos", inflight=10) == 2.0
    # queue pressure: scales with live queue depth
    assert ac.compute_retry_after("backpressure", queue_depth=5) == 1.5
    assert ac.compute_retry_after("deadline", queue_depth=10) == 2.0
    # kv pressure: scales with actively decoding sequences
    assert ac.compute_retry_after("kv_exhausted", active=10) == 2.0
    assert ac.compute_retry_after("kv_exhausted", active=0) == 1.0
    # unknown reasons get the base hint
    assert ac.compute_retry_after("???") == 1.0


def test_shed_headers_carry_live_retry_after(decode_sess):
    """HTTP-level: each reachable shed reason answers with the header
    computed from live state."""
    gw = Gateway(capacity=1)
    try:
        gw.add_decode("tiny", decode_sess)
        # qos: fill the only slot, then shed
        assert gw.admission.try_acquire("tiny")
        st, hdrs, raw = _post(gw.port, "/v1/generate",
                              {"model": "tiny", "prompt": [1]})
        assert st == 429
        assert json.loads(raw)["error"] == "qos"
        assert float(hdrs["Retry-After"]) == pytest.approx(
            gw.admission.compute_retry_after("qos"), abs=0.5)
        gw.admission.release("tiny")
        # shutdown: drain flips every new request to 503 + long hint
        gw.drain()
        st, hdrs, raw = _post(gw.port, "/v1/generate",
                              {"model": "tiny", "prompt": [1]})
        assert st == 503
        assert json.loads(raw)["error"] == "shutdown"
        assert float(hdrs["Retry-After"]) >= 10.0
    finally:
        gw._draining.clear()
        gw.close()


def test_drain_flips_readyz_not_healthz(decode_sess):
    """Liveness says "restart me", readiness says "route away": a drain
    must flip only readiness, or the balancer's health check kills a
    process that is finishing real work."""
    gw = Gateway()
    try:
        gw.add_decode("tiny", decode_sess)
        assert _get(gw.port, "/healthz")[0] == 200
        assert _get(gw.port, "/readyz")[0] == 200
        gw.drain()
        assert gw.draining
        st, raw = _get(gw.port, "/readyz")
        assert st == 503
        assert json.loads(raw)["components"]["gateway:gateway"] is False
        assert _get(gw.port, "/healthz")[0] == 200        # still alive
    finally:
        gw._draining.clear()
        gw.close()


def test_open_breaker_flips_readyz_not_healthz():
    """A batcher's open circuit breaker is a routing signal, not a
    liveness failure."""
    net = _make_net(0.1)
    rt = ModelRuntime(net, item_shapes=ITEM, max_batch=2)
    reg = ModelRegistry()
    reg.register("m", rt, max_latency_ms=1.0)
    gw = Gateway(registry=reg)
    try:
        assert _get(gw.port, "/readyz")[0] == 200
        b = reg.get("m")
        b._breaker_open_until = time.perf_counter() + 60.0
        st, raw = _get(gw.port, "/readyz")
        assert st == 503
        assert json.loads(raw)["components"][f"batcher:{rt.name}"] is False
        assert _get(gw.port, "/healthz")[0] == 200
        b._breaker_open_until = 0.0
        assert _get(gw.port, "/readyz")[0] == 200
    finally:
        gw.close()
        reg.close(drain=False)


def test_sse_client_disconnect_aborts_decode(decode_sess):
    """Satellite 1: the SSE reader hangs up mid-stream -> the gateway
    aborts the decode via the scheduler, the KV pages come back, and
    the eviction is accounted reason="aborted" — no leaked slots, no
    tokens decoded for nobody."""
    from mxnet_tpu.resilience import faults

    import socket as socketlib

    telemetry.enable()
    gw = Gateway()
    sock = None
    try:
        gw.add_decode("tiny", decode_sess)
        base_pages = decode_sess.stats()["pages_in_use"]
        with faults.scope("decode.step:delay:40ms"):   # slow the decode
            body = json.dumps({"model": "tiny", "prompt": [5, 9, 2],
                               "max_new_tokens": 29,
                               "stream": True}).encode()
            sock = socketlib.create_connection(("127.0.0.1", gw.port),
                                               timeout=30)
            sock.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                         b"Host: x\r\nContent-Type: application/json\r\n"
                         b"Content-Length: %d\r\n\r\n" % len(body) + body)
            buf = b""
            while b"data: " not in buf:    # headers + first token frame
                chunk = sock.recv(4096)
                assert chunk, "stream closed before first token"
                buf += chunk
            assert b" 200 " in buf.split(b"\r\n", 1)[0]
            sock.close()                   # ...and vanish mid-stream
            sock = None
            # the abort lands at the next step boundary
            deadline = time.perf_counter() + 15.0
            aborted = 0
            while time.perf_counter() < deadline:
                by_label = telemetry.snapshot()["counters_by_label"]
                aborted = sum(
                    v for k, v in
                    by_label.get("decode.evictions", {}).items()
                    if 'reason="aborted"' in k)
                if aborted and \
                        decode_sess.stats()["pages_in_use"] <= base_pages:
                    break
                time.sleep(0.05)
        assert aborted >= 1
        stats = decode_sess.stats()
        assert stats["pages_in_use"] <= base_pages      # pages came back
        assert stats["active"] == 0 and stats["pending"] == 0
        counters = telemetry.snapshot()["counters"]
        assert counters.get("gateway.client_disconnects", 0) >= 1
        # the admission slot was released too
        assert gw.admission.inflight() == 0
    finally:
        if sock is not None:
            sock.close()
        gw.close()


def test_sigterm_drains_gracefully():
    """Satellite 4 (subprocess drill): SIGTERM mid-request -> the
    in-flight request completes 200, new submits shed 503 shutdown,
    and the worker exits 0."""
    import os
    import signal
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__),
                          "gateway_drain_worker.py")
    proc = subprocess.Popen([sys.executable, worker],
                            stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT ")
        port = int(line.split()[1])

        results = {}

        def inflight():
            results["inflight"] = _post(
                port, "/v1/infer",
                {"model": "tiny_dense", "inputs": [0.5] * 8}, timeout=30)

        t = threading.Thread(target=inflight, daemon=True)
        t.start()
        time.sleep(0.15)                 # request is inside the batcher
        proc.send_signal(signal.SIGTERM)
        time.sleep(0.15)                 # drain has flipped
        st, hdrs, raw = _post(port, "/v1/infer",
                              {"model": "tiny_dense",
                               "inputs": [0.5] * 8}, timeout=10)
        assert st == 503
        assert json.loads(raw)["error"] == "shutdown"
        assert float(hdrs["Retry-After"]) >= 10.0
        t.join(timeout=30)
        st, _, raw = results["inflight"]
        assert st == 200                 # in-flight work was not dropped
        assert len(json.loads(raw)["outputs"]) == 4
        out, _ = proc.communicate(timeout=30)
        assert "DRAINED" in out
        assert proc.returncode == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
