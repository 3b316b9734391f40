"""Live observability endpoint: /metrics, /healthz, /trace over stdlib http.

The serving-front-door roadmap item needs a readiness surface a load
balancer / Prometheus scraper / engineer-with-curl can hit without
touching the Python process.  This is it, deliberately tiny: a
``ThreadingHTTPServer`` on localhost (opt-in via ``MXNET_METRICS_PORT``
or :func:`start_server`) dispatching through ONE mutable **route
table**.  The built-in routes:

- ``GET /metrics`` — Prometheus text exposition
  (:func:`..exporters.dump_metrics`): every counter, gauge, span
  aggregate and histogram the bus holds.
- ``GET /healthz`` — **liveness**: 200 when every registered health
  probe says healthy, 503 otherwise.  Liveness answers "should the
  orchestrator restart this process?" — so it covers process-level
  wedges only, never load or drain state.
- ``GET /readyz`` — **readiness**: 200 when every readiness probe says
  ready.  Readiness answers "should a balancer route traffic here right
  now?" — ``Batcher`` and ``DecodeScheduler`` auto-register their
  circuit-breaker state on construction (weakly — a dropped component
  never pins or poisons the endpoint), the gateway registers its
  drain/owner-connectivity state, so the route flips the moment a
  breaker opens, a drain starts, or the device-owner goes away, without
  ever telling the orchestrator to kill a perfectly live process.
- ``GET /trace`` — the current merged chrome trace
  (:func:`..trace.chrome_trace`), loadable straight into Perfetto.

Other subsystems mount onto the SAME server via :func:`register_route` —
``mxnet_tpu.serving.gateway`` adds ``POST /v1/generate`` /
``POST /v1/infer`` this way, so one process exposes one port, and the
one atexit hook here is the only shutdown path (no second server, no
double-shutdown races).  A route handler receives the live
``BaseHTTPRequestHandler`` — full control over the response, including
chunked / SSE streaming straight to the socket.

The server thread is a daemon AND registered with atexit for a bounded
join, so interpreter exit never hangs on an open socket.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import exporters

__all__ = ["start_server", "stop_server", "server_port",
           "register_health", "unregister_health", "health", "devices",
           "register_ready", "unregister_ready", "readiness",
           "register_route", "unregister_route", "routes"]

# ------------------------------------------- health/readiness probe registries
# Two registries, one mechanic.  Liveness (``/healthz``) is "restart me
# if false"; readiness (``/readyz``) is "don't route to me right now".
# Conflating them is the classic outage amplifier: a breaker opening
# under load flips readiness, and a liveness probe wired to the same
# surface would have the orchestrator kill-looping a healthy process.
# A lock its holder may take again: a dead ``DecodeScheduler`` is cyclic
# garbage whose ``__del__`` unregisters it, and the collector may run that on
# the thread that holds this lock (``_register`` allocates a weakref under
# it).  The sections below are dict operations, whole before and after any
# point at which the collector can come in.
_health_lock = threading.RLock()
_health = {}        # name -> weakref to an object with .healthy
_ready = {}         # name -> weakref to an object with .ready (or .healthy)


def _register(registry, name, obj):
    with _health_lock:
        registry[name] = weakref.ref(obj)


def _unregister(registry, name, obj):
    with _health_lock:
        ref = registry.get(name)
        if ref is None:
            return
        if obj is None or ref() is obj or ref() is None:
            del registry[name]


def _report(registry, attrs):
    with _health_lock:
        items = list(registry.items())
    report, ok = {}, True
    for name, ref in items:
        obj = ref()
        if obj is None:
            with _health_lock:
                if registry.get(name) is ref:
                    del registry[name]
            continue
        try:
            h = None
            for attr in attrs:
                h = getattr(obj, attr, None)
                if h is not None:
                    break
            if callable(h):
                h = h()
            h = bool(h)
        except Exception:
            h = False
        report[name] = h
        ok = ok and h
    return ok, report


def register_health(name, obj):
    """Register a **liveness** probe: ``obj`` (anything exposing
    ``.healthy`` — property or nullary method) under ``name``.  Weakly
    referenced: a collected component silently drops out instead of
    failing health forever."""
    _register(_health, name, obj)


def unregister_health(name, obj=None):
    """Remove a liveness probe.  With ``obj`` given, remove only if the
    entry still points at it — so ``registry.swap()`` patterns where a new
    component registered under the same name don't get torn down by the
    old one's close()."""
    _unregister(_health, name, obj)


def health():
    """``(ok, {name: bool})`` across live liveness probes.  A probe that
    raises counts as unhealthy; a dead weakref is dropped."""
    return _report(_health, ("healthy",))


def devices():
    """``{name: ...}`` for every liveness probe that exposes ``.devices``
    (property or nullary method): where that component's models run, by
    jax ``platform``/``device_kind``.  A server that landed on the host
    says so on the probe an operator already watches."""
    with _health_lock:
        items = list(_health.items())
    out = {}
    for name, ref in items:
        d = getattr(ref(), "devices", None)
        if callable(d):
            d = d()
        if d:
            out[name] = d
    return out


def register_ready(name, obj):
    """Register a **readiness** probe under ``name``: ``obj.ready`` is
    consulted, falling back to ``obj.healthy`` (so breaker-bearing
    components register once and mean it).  Weakly referenced, like
    :func:`register_health`."""
    _register(_ready, name, obj)


def unregister_ready(name, obj=None):
    """Remove a readiness probe (same ``obj``-guard as
    :func:`unregister_health`)."""
    _unregister(_ready, name, obj)


def readiness():
    """``(ok, {name: bool})`` across live readiness probes."""
    return _report(_ready, ("ready", "healthy"))


# -------------------------------------------------------------- route table
_routes_lock = threading.Lock()
_routes = {}        # (METHOD, path) -> callable(handler)


def register_route(method, path, fn):
    """Mount ``fn`` at ``(method, path)`` on the shared server.  ``fn``
    receives the live ``BaseHTTPRequestHandler`` (use ``_send`` /
    ``send_json`` / ``read_body``, or write to ``handler.wfile`` directly
    for streaming responses).  Last registration wins — hot-swap by
    re-registering."""
    with _routes_lock:
        _routes[(method.upper(), path)] = fn


def unregister_route(method, path, fn=None):
    """Unmount a route.  With ``fn`` given, remove only if the table still
    points at it — a new owner's mount survives the old owner's close()."""
    with _routes_lock:
        key = (method.upper(), path)
        cur = _routes.get(key)
        if cur is None:
            return
        if fn is None or cur is fn:
            del _routes[key]


def routes():
    """Snapshot of the mounted ``(method, path)`` pairs."""
    with _routes_lock:
        return sorted(_routes)


def _route_metrics(h):
    h._send(200, exporters.dump_metrics())


def _route_healthz(h):
    ok, report = health()
    body = json.dumps({"ok": ok, "components": report,
                       "devices": devices()}) + "\n"
    h._send(200 if ok else 503, body, "application/json")


def _route_readyz(h):
    ok, report = readiness()
    body = json.dumps({"ok": ok, "components": report}) + "\n"
    h._send(200 if ok else 503, body, "application/json")


def _route_trace(h):
    from . import trace
    h._send(200, json.dumps(trace.chrome_trace()), "application/json")


register_route("GET", "/metrics", _route_metrics)
register_route("GET", "/healthz", _route_healthz)
register_route("GET", "/readyz", _route_readyz)
register_route("GET", "/trace", _route_trace)


# ----------------------------------------------------------------- the server
_server_lock = threading.Lock()
_server = None
_thread = None


class _Handler(BaseHTTPRequestHandler):
    # HTTP/1.1: fixed-length responses keep the connection alive (every
    # _send sets Content-Length); streaming handlers opt out by sending
    # ``Connection: close`` and writing until done (SSE frames)
    protocol_version = "HTTP/1.1"

    def _send(self, code, body, ctype="text/plain; charset=utf-8",
              headers=None):
        data = body.encode() if isinstance(body, str) else body
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(data)

    def send_json(self, code, obj, headers=None):
        self._send(code, json.dumps(obj) + "\n", "application/json",
                   headers=headers)

    def read_body(self, limit=16 * 1024 * 1024):
        """The request body (b"" when absent); 413-sized bodies raise."""
        n = int(self.headers.get("Content-Length") or 0)
        if n > limit:
            raise ValueError(f"request body of {n} bytes exceeds {limit}")
        return self.rfile.read(n) if n > 0 else b""

    def _dispatch(self, method):
        path = self.path.split("?", 1)[0]
        with _routes_lock:
            fn = _routes.get((method, path))
        if fn is None:
            try:
                self._send(404, "not found\n")
            except OSError:
                pass
            return
        try:
            fn(self)
        except Exception as e:     # noqa: BLE001 — a request must not kill us
            try:
                self._send(500, f"error: {e!r}\n")
            except (OSError, ValueError):
                pass       # headers already sent / peer gone

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def log_message(self, *args):  # noqa: D102 — silence per-request stderr
        pass


def start_server(port=0, host="127.0.0.1"):
    """Start the endpoint (idempotent); returns the bound port.  ``port=0``
    binds an ephemeral port — the return value is how tests find it."""
    global _server, _thread
    with _server_lock:
        if _server is not None:
            return _server.server_address[1]
        _server = ThreadingHTTPServer((host, int(port)), _Handler)
        _server.daemon_threads = True
        _thread = threading.Thread(target=_server.serve_forever,
                                   kwargs={"poll_interval": 0.2},
                                   name="telemetry-http", daemon=True)
        _thread.start()
        return _server.server_address[1]


def stop_server(timeout=5.0):
    """Shut the endpoint down with a bounded join (also runs at atexit, so
    interpreter teardown never hangs on the serve loop)."""
    global _server, _thread
    with _server_lock:
        srv, thr = _server, _thread
        _server = _thread = None
    if srv is None:
        return
    try:
        srv.shutdown()
        srv.server_close()
    except OSError:
        pass
    if thr is not None and thr.is_alive():
        thr.join(timeout=timeout)


def server_port():
    """The bound port, or None when the server is down."""
    with _server_lock:
        return _server.server_address[1] if _server is not None else None


atexit.register(stop_server)

if os.environ.get("MXNET_METRICS_PORT"):
    start_server(int(os.environ["MXNET_METRICS_PORT"]))
