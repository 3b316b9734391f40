"""serving.fleet: crash-supervised device-owner + fault-tolerant RPC
(ISSUE 19 tentpole).

Layered coverage: frame codec (crc, magic, size cap, restricted
unpickler), client/server RPC semantics over a real AF_UNIX socket
(deadline propagation, typed error mapping, streaming, cancel,
heartbeats), transport fault sites (``fleet.rpc_send`` redial), and the
supervisor (spawn readiness, SIGKILL auto-restart with generation bump,
``fleet.owner_spawn`` retry under backoff).  The full chaos drill —
200 concurrent HTTP requests across two owner kills — lives in the CI
``fleet`` stage, not here.
"""
import os
import pickle
import signal
import socket
import threading
import time
import zlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.resilience import faults
from mxnet_tpu.resilience.retry import RetryPolicy
from mxnet_tpu.serving.batcher import RequestRejected
from mxnet_tpu.serving.fleet import (FrameError, OwnerClient, OwnerGone,
                                     RemoteError, RPCServer)
from mxnet_tpu.serving.fleet import transport as T


@pytest.fixture(autouse=True)
def _clean():
    telemetry.disable()
    telemetry.reset()
    faults.clear()
    yield
    telemetry.disable()
    telemetry.reset()
    faults.clear()


# ------------------------------------------------------------ frame codec
def _pair():
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    return a, b


def test_frame_roundtrip_all_kinds():
    a, b = _pair()
    try:
        for kind in (T.REQ, T.RES, T.STREAM, T.PING, T.PONG, T.CANCEL):
            payload = {"id": kind, "blob": np.arange(kind + 1.0),
                       "nested": {"k": [1, 2, 3]}}
            T.send_frame(a, kind, payload)
            got_kind, got = T.recv_frame(b)
            assert got_kind == kind
            assert got["id"] == kind
            np.testing.assert_array_equal(got["blob"], payload["blob"])
            assert got["nested"] == payload["nested"]
    finally:
        a.close()
        b.close()


def test_frame_crc_mismatch_rejected():
    a, b = _pair()
    try:
        data = pickle.dumps({"x": 1})
        bad_crc = (zlib.crc32(data) ^ 0xdead) & 0xffffffff
        frame = T._HEADER.pack(T._MAGIC, T.RES, len(data), bad_crc)
        a.sendall(frame + data)
        with pytest.raises(FrameError, match="crc"):
            T.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_frame_bad_magic_rejected():
    a, b = _pair()
    try:
        a.sendall(T._HEADER.pack(b"NOPE", T.RES, 0, 0))
        with pytest.raises(FrameError, match="magic"):
            T.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_frame_oversize_rejected():
    a, b = _pair()
    try:
        a.sendall(T._HEADER.pack(T._MAGIC, T.RES, T.MAX_FRAME + 1, 0))
        with pytest.raises(FrameError, match="exceeds"):
            T.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_frame_eof_is_owner_gone():
    a, b = _pair()
    a.close()
    try:
        with pytest.raises(OwnerGone):
            T.recv_frame(b)
    finally:
        b.close()


def test_restricted_unpickler_blocks_foreign_classes():
    # any non-numpy/builtins class is refused — even this framework's own
    evil = pickle.dumps(RetryPolicy())
    with pytest.raises(pickle.UnpicklingError, match="forbidden"):
        T._loads(evil)
    # the allowed surface (numpy + builtins) round-trips
    ok = T._loads(T._dumps({"a": np.float32(2.5), "b": [1, "x"]}))
    assert ok["a"] == np.float32(2.5)


# ----------------------------------------------------- RPC client / server
class EchoService:
    """Duck-typed service capturing what the wire delivered."""

    def __init__(self):
        self.seen = []            # (method, params, deadline_ms, trace)
        self.cancelled = []
        self.release = threading.Event()

    def pong(self):
        return {"pid": os.getpid(), "generation": 7}

    def cancel(self, key):
        self.cancelled.append(key)
        self.release.set()

    def handle(self, method, params, deadline_ms, trace, emit,
               register_cancel):
        self.seen.append((method, dict(params), deadline_ms, trace))
        if method == "echo":
            return {"echo": params}
        if method == "boom_key":
            raise KeyError("no such model")
        if method == "boom_value":
            raise ValueError("bad arg")
        if method == "boom_reject":
            raise RequestRejected("backpressure", "queue full")
        if method == "boom_bug":
            raise RuntimeError("owner bug")
        if method == "slow":
            self.release.wait(timeout=10.0)
            return {"done": True}
        if method == "stream":
            register_cancel("req-key")
            for i in range(int(params["n"])):
                emit({"token": i * 10, "index": i})
            return {"count": int(params["n"])}
        if method == "stream_cancel":
            register_cancel("req-key")
            emit({"token": 0, "index": 0})
            self.release.wait(timeout=10.0)
            return {"count": 1, "cancelled": bool(self.cancelled)}
        raise KeyError(method)


@pytest.fixture()
def rpc(tmp_path):
    path = str(tmp_path / "owner.sock")
    svc = EchoService()
    server = RPCServer(path, svc)
    client = OwnerClient(path, retry=RetryPolicy(
        max_attempts=4, base_delay_ms=10.0, max_delay_ms=50.0, seed=0))
    yield svc, server, client, path
    client.close()
    server.close()


def test_rpc_roundtrip_and_deadline_propagation(rpc):
    svc, _server, client, _ = rpc
    out = client.call("echo", {"x": 1}, deadline_ms=1234.5)
    assert out == {"echo": {"x": 1}}
    method, params, deadline, _trace = svc.seen[0]
    assert method == "echo" and params == {"x": 1}
    assert deadline == pytest.approx(1234.5)   # rode the wire


def test_rpc_trace_context_rides_frames(rpc):
    svc, _server, client, _ = rpc

    class Ctx:
        trace_id, span_id = 0xabc, 0xdef

    client.call("echo", {}, trace=Ctx())
    assert tuple(svc.seen[0][3]) == (0xabc, 0xdef)


def test_rpc_typed_error_mapping(rpc):
    _svc, _server, client, _ = rpc
    with pytest.raises(KeyError):
        client.call("boom_key")
    with pytest.raises(ValueError, match="bad arg"):
        client.call("boom_value")
    with pytest.raises(RequestRejected) as ei:
        client.call("boom_reject")
    assert ei.value.reason == "backpressure"
    with pytest.raises(RemoteError, match="owner bug"):
        client.call("boom_bug")
    # the server survives every one of those
    assert client.call("echo", {"ok": 1}) == {"echo": {"ok": 1}}


def test_rpc_streaming_and_terminal_result(rpc):
    _svc, _server, client, _ = rpc
    stream = client.stream("stream", {"n": 4}, deadline_ms=10_000)
    frames = list(stream)
    assert [f["token"] for f in frames] == [0, 10, 20, 30]
    assert stream.result() == {"count": 4}


def test_rpc_stream_cancel_routes_to_service(rpc):
    svc, _server, client, _ = rpc
    stream = client.stream("stream_cancel", {}, timeout=10.0)
    first = next(iter(stream))
    assert first["token"] == 0
    stream.cancel()
    assert svc.release.wait(timeout=5.0)
    assert stream.result()["cancelled"] is True
    assert svc.cancelled == ["req-key"]


def test_rpc_ping_heartbeat(rpc):
    _svc, _server, client, _ = rpc
    pong = client.ping(timeout=2.0)
    assert pong["pid"] == os.getpid() and pong["generation"] == 7


def test_rpc_heartbeat_answers_while_request_runs(rpc):
    svc, _server, client, _ = rpc
    done = {}

    def slow():
        done["r"] = client.call("slow", timeout=10.0)

    t = threading.Thread(target=slow, daemon=True)
    t.start()
    time.sleep(0.1)
    assert client.ping(timeout=2.0)["generation"] == 7   # not head-blocked
    svc.release.set()
    t.join(timeout=5.0)
    assert done["r"] == {"done": True}


def test_rpc_call_timeout(rpc):
    _svc, _server, client, _ = rpc
    with pytest.raises(TimeoutError):
        client.call("slow", timeout=0.2)


def test_server_death_fails_outstanding_calls_with_owner_gone(rpc):
    svc, server, client, _ = rpc
    errs = []

    def slow():
        try:
            client.call("slow", timeout=10.0)
        except Exception as e:          # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=slow, daemon=True)
    t.start()
    time.sleep(0.1)
    server.close()
    t.join(timeout=5.0)
    assert len(errs) == 1 and isinstance(errs[0], OwnerGone)
    svc.release.set()


def test_rpc_send_fault_tears_call_next_call_redials(rpc, tmp_path):
    _svc, _server, client, _ = rpc
    telemetry.enable()
    client.call("echo", {"warm": 1})          # established connection
    with faults.scope("fleet.rpc_send:fail:1"):
        # a torn send is OwnerGone for THIS call — retrying an
        # idempotent request is the caller's (gateway's) decision
        with pytest.raises(OwnerGone):
            client.call("echo", {"x": 2})
    out = client.call("echo", {"x": 3})       # next call redials
    assert out == {"echo": {"x": 3}}
    assert client.reconnects >= 1
    snap = telemetry.snapshot()["counters"]
    assert snap.get("fleet.transport_failures", 0) >= 1
    assert snap.get("fleet.reconnects", 0) >= 1


def test_client_without_retry_raises_on_dead_socket(tmp_path):
    client = OwnerClient(str(tmp_path / "nothing.sock"),
                         retry=RetryPolicy(max_attempts=1))
    with pytest.raises(OSError):
        client.call("echo", {})
    client.close()


def test_stale_socket_file_is_replaced(tmp_path):
    path = str(tmp_path / "stale.sock")
    left = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    left.bind(path)                     # a SIGKILLed predecessor's leavings
    left.close()
    svc = EchoService()
    server = RPCServer(path, svc)
    client = OwnerClient(path)
    try:
        assert client.call("echo", {"a": 1}) == {"echo": {"a": 1}}
    finally:
        client.close()
        server.close()
    assert not os.path.exists(path)     # close() unlinks


# -------------------------------------------------------------- supervisor
EMPTY_SPEC = "tests.fleet_builder:build_empty"


def _fast_supervisor(tmp_path, **kw):
    from mxnet_tpu.serving.fleet import Supervisor
    kw.setdefault("heartbeat_s", 0.2)
    kw.setdefault("backoff", RetryPolicy(max_attempts=4, base_delay_ms=20.0,
                                         max_delay_ms=100.0, seed=0))
    kw.setdefault("stable_s", 0.5)
    return Supervisor(EMPTY_SPEC, str(tmp_path / "owner.sock"), **kw)


def test_supervisor_spawn_ping_stats_stop(tmp_path):
    sup = _fast_supervisor(tmp_path)
    sup.start()
    try:
        assert sup.alive
        cli = sup.client()
        pong = cli.ping(timeout=5.0)
        assert pong["pid"] == sup.owner_pid
        assert pong["generation"] == 0
        stats = cli.call("stats", timeout=10.0)
        assert stats["pid"] == sup.owner_pid
        assert stats["infer_models"] == []
        cli.close()
    finally:
        sup.stop()
    assert not sup.alive
    assert not os.path.exists(sup.socket_path)


def test_supervisor_restarts_after_sigkill(tmp_path):
    telemetry.enable()
    sup = _fast_supervisor(tmp_path)
    sup.start()
    try:
        pid0 = sup.owner_pid
        os.kill(pid0, signal.SIGKILL)
        deadline = time.perf_counter() + 30.0
        # restarts counts the death at once; the replacement is up (socket
        # bound) only when the supervisor holds a live process again
        while time.perf_counter() < deadline and \
                not (sup.restarts >= 1 and sup.alive):
            time.sleep(0.05)
        assert sup.restarts == 1
        assert sup.generation == 1
        # the replacement answers, with a new pid and the bumped generation
        cli = sup.client()
        pong = cli.ping(timeout=10.0)
        assert pong["pid"] == sup.owner_pid != pid0
        assert pong["generation"] == 1
        cli.close()
        snap = telemetry.snapshot()["counters"]
        assert snap.get("fleet.owner_restarts", 0) >= 1
    finally:
        sup.stop()


def test_supervisor_owner_spawn_fault_retried(tmp_path):
    faults.inject("fleet.owner_spawn", "fail:1")
    sup = _fast_supervisor(tmp_path)
    try:
        sup.start()                     # first spawn injected dead, retried
        assert sup.alive
        cli = sup.client()
        assert cli.ping(timeout=5.0)["generation"] == 0
        cli.close()
    finally:
        sup.stop()


def test_supervisor_spawn_gives_up_after_budget(tmp_path):
    from mxnet_tpu.serving.fleet import Supervisor
    faults.inject("fleet.owner_spawn", "fail:10")
    sup = Supervisor(EMPTY_SPEC, str(tmp_path / "owner.sock"),
                     backoff=RetryPolicy(max_attempts=2, base_delay_ms=5.0,
                                         seed=0))
    with pytest.raises(faults.InjectedFault):
        sup.start()
    sup.stop()


# ------------------------------------------------- multi-front-end drill
@pytest.mark.slow
def test_two_gateway_frontends_share_one_owner(tmp_path):
    """The scale-out topology: two gateway *processes* (separate HTTP
    front doors, separate crash domains) proxy one supervised device
    owner over its unix socket.  Both answer 200 with bitwise-identical
    tokens, keep answering after the owner is SIGKILLed and respawned
    (each front end redials the socket on its next call — no front-end
    restart, no lost port), and the fleet socket is the ONLY thing the
    front ends share."""
    import http.client
    import json
    import subprocess
    import sys

    from mxnet_tpu.serving.fleet import Supervisor

    sup = Supervisor("tests.fleet_builder:build",
                     str(tmp_path / "owner.sock"),
                     aot_cache=str(tmp_path / "aot"), heartbeat_s=0.3)
    sup.start()
    procs, ports = [], []

    def post(port, body, timeout=120):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        try:
            conn.request("POST", "/v1/generate", json.dumps(body),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, r.read()
        finally:
            conn.close()

    body = {"model": "decode_tiny", "prompt": [5, 9, 2],
            "max_new_tokens": 6, "temperature": 0.8, "seed": 11,
            "deadline_ms": 60000}
    try:
        for _ in range(2):
            p = subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(__file__),
                              "gateway_frontend_worker.py"),
                 "--socket", sup.socket_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            procs.append(p)
            hello = json.loads(p.stdout.readline())
            ports.append(hello["port"])
        assert ports[0] != ports[1]
        ref = None
        for port in ports:
            st, raw = post(port, body)
            assert st == 200, (port, st, raw)
            toks = json.loads(raw)["token_ids"]
            ref = toks if ref is None else ref
            assert toks == ref, (port, toks, ref)
        pid0 = sup.owner_pid
        os.kill(pid0, signal.SIGKILL)
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline and sup.restarts < 1:
            time.sleep(0.05)
        assert sup.restarts >= 1 and sup.owner_pid != pid0
        # both front ends keep serving the SAME bitwise stream through
        # the replacement owner — no front-end process was touched.
        # While the replacement binds its socket the documented
        # degradation is 503 owner_unavailable (+ Retry-After), never a
        # 5xx crash or a dead port — so: retry until 200, tolerating
        # ONLY 503 in between.
        for port in ports:
            deadline = time.perf_counter() + 60.0
            while True:
                st, raw = post(port, body)
                if st == 200:
                    break
                assert st == 503, (port, st, raw)
                assert time.perf_counter() < deadline, (port, raw)
                time.sleep(0.2)
            assert json.loads(raw)["token_ids"] == ref
    finally:
        for p in procs:
            try:
                p.stdin.close()
                p.wait(timeout=10)
            except Exception:
                p.kill()
        sup.stop()
    assert not os.path.exists(sup.socket_path)
