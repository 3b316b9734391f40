"""The HTTP front door: routes, SSE streaming, shed→status mapping.

Design rules (each one traceable in the handler code):

- **One server.**  Routes mount on the shared ``telemetry.http`` route
  table — ``/metrics``, ``/healthz``, ``/readyz``, ``/trace`` and the
  gateway's ``/v1/*`` answer on the same port, shut down by the one
  atexit hook.
- **The trace lane starts at the wire.**  A ``TraceContext`` is minted
  the moment a request is parsed; ``submit()`` runs under it, so the
  scheduler's whole per-request lane (queue wait, prefill, every ride)
  hangs off the socket-level root — and in proxy mode the context rides
  the RPC frames, so the lane spans both processes.
- **Shedding is a status code, not an exception.**  Every
  ``RequestRejected`` reason maps to exactly one HTTP answer —
  retryable pressure (``deadline`` / ``kv_exhausted`` / ``qos`` /
  ``backpressure``) ⇒ 429, down-ness (``unhealthy`` breaker /
  ``shutdown`` / a dead device-owner) ⇒ 503 — both with a **live**
  ``Retry-After`` computed from the state that caused the shed
  (:meth:`~.qos.AdmissionController.compute_retry_after`).  Malformed ⇒
  400, unknown model ⇒ 404.  5xx is reserved for actual bugs.
- **Streaming is an observer.**  ``stream=true`` rides the scheduler's
  :class:`~mxnet_tpu.serving.decode.TokenStream` — the buffered path's
  token sequence is bitwise what the SSE frames carry (CI-asserted).
  A client that hangs up mid-stream aborts the session at the next step
  boundary (KV pages freed, ``decode.evictions`` ``reason="aborted"``).
- **Degradation is graceful, in both directions.**  With
  ``Gateway(owner=...)`` the models live in a separate crash-supervised
  device-owner process: idempotent ``/v1/infer`` calls are transparently
  retried against the restarted owner within their deadline; an
  in-flight SSE stream whose owner dies ends with a *terminal error
  frame* plus ``[DONE]`` (never a torn stream); buffered requests get an
  honest 503 + ``Retry-After``.  ``SIGTERM`` (via
  :meth:`install_preemption`) drains: stop admitting (503 ``shutdown``),
  finish in-flight, flip ``/readyz`` — liveness stays green the whole
  time, so the orchestrator never kill-loops a draining process.

SSE frame format (``Content-Type: text/event-stream``, connection
closes at end of stream)::

    data: {"token": 17, "index": 0}\n\n      # one per generated token
    data: {"done": true, "finish_reason": "length", ...}\n\n
    data: [DONE]\n\n
"""
from __future__ import annotations

import json
import threading
import time

import numpy as np

from ...telemetry import bus as _tel
from ...telemetry import flight as _flight
from ...telemetry import http as _http
from ...telemetry import trace as _trace
from ..batcher import RequestRejected
from ..runtime import device_info
from .qos import AdmissionController

__all__ = ["Gateway"]

# RequestRejected reason -> HTTP status.  429: retry the same box later
# (pressure, not failure).  503: this box is not serving (breaker open /
# shutting down) — a balancer should fail over.
_REJECT_STATUS = {
    "deadline": 429,
    "kv_exhausted": 429,
    "backpressure": 429,
    "qos": 429,
    "shutdown": 503,
    "unhealthy": 503,
}


class Gateway:
    """HTTP front door over a :class:`~mxnet_tpu.serving.ModelRegistry`
    (``POST /v1/infer``) and named decode sessions (``POST
    /v1/generate``), with weighted QoS admission control.

    Parameters
    ----------
    registry : ModelRegistry, optional
        Batcher models served by ``/v1/infer`` (in-process mode).
    admission : AdmissionController, optional
        Shared admission gate; built from ``capacity`` when omitted.
    capacity : int
        In-flight bound for the default controller.
    port : int
        Port for the shared telemetry/gateway server (0 = ephemeral; the
        bound port is :attr:`port`).  If the server is already up, its
        existing port wins — one process, one port.
    default_deadline_ms : float, optional
        Deadline applied to requests that don't carry one.
    owner : Supervisor, OwnerClient or str, optional
        Proxy mode: route ``/v1/*`` over the fleet RPC transport to a
        device-owner process instead of in-process models.  A
        :class:`~mxnet_tpu.serving.fleet.Supervisor` (its socket +
        restart state feed readiness), a ready-made
        :class:`~mxnet_tpu.serving.fleet.OwnerClient`, or a socket path.
    infer_retry_budget_ms : float
        Retry window for ``/v1/infer`` requests that carry no deadline —
        how long the gateway keeps retrying against a restarting owner
        before answering 503.
    """

    def __init__(self, registry=None, admission=None, capacity=64,
                 port=0, default_deadline_ms=None, name="gateway",
                 owner=None, infer_retry_budget_ms=10_000.0):
        self.registry = registry
        self.name = name
        self.admission = admission if admission is not None \
            else AdmissionController(capacity)
        self.default_deadline_ms = default_deadline_ms
        self.infer_retry_budget_ms = float(infer_retry_budget_ms)
        self._decode = {}
        self._closed = False
        self._draining = threading.Event()
        self._preempt_watch = None
        self.owner = None
        self._supervisor = None
        self._owns_client = False
        if owner is not None:
            # local import: non-proxy gateways never pay for (or depend
            # on) the fleet machinery
            from ..fleet.supervisor import Supervisor
            from ..fleet.transport import OwnerClient
            if isinstance(owner, Supervisor):
                self._supervisor = owner
                self.owner = owner.client()
                self._owns_client = True
            elif isinstance(owner, OwnerClient):
                self.owner = owner
            else:
                self.owner = OwnerClient(str(owner))
                self._owns_client = True
        self._mounts = [
            ("POST", "/v1/generate", self._route_generate),
            ("POST", "/v1/infer", self._route_infer),
        ]
        for method, path, fn in self._mounts:
            _http.register_route(method, path, fn)
        _http.register_health(f"gateway:{name}", self)
        _http.register_ready(f"gateway:{name}", self)
        self.port = _http.start_server(port)

    # ----------------------------------------------------------- model map
    def add_decode(self, name, session, weight=None):
        """Expose a :class:`~mxnet_tpu.serving.decode.DecodeSession` (or
        ``DecodeScheduler``) as ``model=name`` on ``/v1/generate``."""
        self._decode[name] = session
        if weight is not None:
            self.admission.set_weight(name, weight)
        return session

    def remove_decode(self, name):
        self._decode.pop(name, None)

    def set_weight(self, model, weight):
        self.admission.set_weight(model, weight)

    @property
    def healthy(self):
        """Liveness: the process-level probe.  Draining and owner
        restarts do NOT flip this — killing a draining process throws
        away the in-flight work the drain exists to finish."""
        return not self._closed

    @property
    def ready(self):
        """Readiness: should a balancer send traffic here right now?
        False while closed, draining, or (proxy mode) while the
        device-owner is down/restarting."""
        if self._closed or self._draining.is_set():
            return False
        if self._supervisor is not None:
            return self._supervisor.alive
        if self.owner is not None:
            if self.owner.connected:
                return True
            try:
                self.owner.ping(timeout=1.0)
                return True
            except Exception:       # noqa: BLE001 — any failure = not ready
                return False
        return True

    @property
    def draining(self):
        return self._draining.is_set()

    @property
    def devices(self):
        """Where the models behind this gateway run — ``{model:
        {"platform", "device_kind"}}`` in process, ``{"owner": ...}`` as
        the device-owner reports it in proxy mode (empty while it is
        down).  Rides ``/healthz``."""
        if self.owner is None:
            return {name: device_info(s.device)
                    for name, s in self._decode.items()}
        try:
            pong = self.owner.ping(timeout=1.0)
        except (OSError, TimeoutError):
            return {}
        return {"owner": {k: pong.get(k)
                          for k in ("platform", "device_kind")}}

    # ---------------------------------------------------------------- drain
    def drain(self):
        """Stop admitting (new requests shed 503 ``shutdown``), let
        in-flight requests finish, flip ``/readyz``.  Idempotent.  The
        SIGTERM path: a balancer watching readiness routes away while
        the last requests complete, then the process exits 0."""
        if self._draining.is_set():
            return
        self._draining.set()
        _flight.record("gateway.drain", detail=self.name)
        if _tel.enabled:
            _tel.count("gateway.drains")
            _tel.instant("gateway.drain", name=self.name)

    def install_preemption(self, handler):
        """Wire a :class:`~mxnet_tpu.resilience.PreemptionHandler` to
        the drain path: on SIGTERM the watcher flips the gateway to
        draining, in-flight requests complete, new submits get 503 —
        and the process is free to exit 0 once traffic stops."""
        def _watch():
            handler.wait()
            self.drain()
        t = threading.Thread(target=_watch, daemon=True,
                             name="gateway-preempt-watch")
        t.start()
        self._preempt_watch = t
        return handler

    # ------------------------------------------------------------- helpers
    def _resolve_decode(self, body):
        name = body.get("model")
        if name is None:
            if len(self._decode) == 1:
                name = next(iter(self._decode))
            else:
                return None, None
        return name, self._decode.get(name)

    def _count(self, route, model, status):
        if _tel.enabled:
            _tel.count("gateway.requests", route=route, model=str(model))
            _tel.count("gateway.responses", status=int(status))

    def _retry_after(self, reason, source=None):
        """Live Retry-After for one shed: pull queue depth / breaker
        cool-down off the component that rejected (best-effort — a
        half-closed component must not turn a clean 429 into a 500)."""
        queue_depth = active = 0
        breaker = None
        if source is not None:
            try:
                breaker = getattr(source, "breaker_remaining_s", None)
            except Exception:        # noqa: BLE001 — probe, not contract
                breaker = None
            try:
                if hasattr(source, "stats"):
                    st = source.stats()
                    queue_depth = int(st.get("pending", 0))
                    active = int(st.get("active", 0))
                elif hasattr(source, "pending"):
                    queue_depth = int(source.pending())
            except Exception:        # noqa: BLE001 — probe, not contract
                pass
        return self.admission.compute_retry_after(
            reason, queue_depth=queue_depth, active=active,
            breaker_remaining_s=breaker)

    def _shed(self, h, route, model, exc, source=None):
        """Answer a RequestRejected with its mapped status + Retry-After."""
        status = _REJECT_STATUS.get(exc.reason, 503)
        retry = self._retry_after(exc.reason, source)
        if _tel.enabled:
            _tel.count("gateway.shed", route=route, reason=exc.reason)
        self._count(route, model, status)
        h.send_json(status,
                    {"error": exc.reason, "detail": str(exc)},
                    headers={"Retry-After": f"{retry:g}"})

    def _owner_unavailable(self, h, route, model, exc):
        """The device-owner died under this request and the retry budget
        ran out: an honest 503 + Retry-After sized to the supervisor's
        AOT-warm restart — never a 5xx from the crash path."""
        retry = self._retry_after("owner_unavailable")
        if _tel.enabled:
            _tel.count("gateway.shed", route=route,
                       reason="owner_unavailable")
        self._count(route, model, 503)
        h.send_json(503, {"error": "owner_unavailable",
                          "detail": str(exc) or repr(exc)},
                    headers={"Retry-After": f"{retry:g}"})

    def _check_admittable(self, h, route, model):
        """Drain/close gate + QoS gate, shared by every route.  Returns
        True with an admission slot held; False with the shed already
        answered."""
        if self._closed or self._draining.is_set():
            self._shed(h, route, model,
                       RequestRejected("shutdown",
                                       "gateway is draining"))
            return False
        if not self.admission.try_acquire(model):
            self._shed(h, route, model,
                       RequestRejected(
                           "qos", f"model {model!r} is past its QoS share "
                                  f"and the gateway is at capacity"))
            return False
        return True

    @staticmethod
    def _bad_request(h, detail):
        h.send_json(400, {"error": "bad_request", "detail": detail})

    def _parse(self, h):
        try:
            body = json.loads(h.read_body().decode() or "{}")
        except (ValueError, UnicodeDecodeError) as e:
            self._bad_request(h, f"malformed JSON body: {e}")
            return None
        if not isinstance(body, dict):
            self._bad_request(h, "body must be a JSON object")
            return None
        return body

    # ---------------------------------------------------- POST /v1/generate
    def _route_generate(self, h):
        if not _tel.enabled:
            self._handle_generate(h)
            return
        # what one request costs the interpreter on its handler thread,
        # from the wire to the last flush: this thread's CPU time, counted
        # beside gateway.responses (two clock reads a request)
        cpu0 = time.thread_time()
        try:
            self._handle_generate(h)
        finally:
            _tel.count("gateway.handler_cpu_ms",
                       (time.thread_time() - cpu0) * 1e3, route="generate")

    def _handle_generate(self, h):
        t_wire = time.perf_counter()
        body = self._parse(h)
        if body is None:
            return
        if self.owner is not None:
            self._proxy_generate(h, body, t_wire)
            return
        model, sess = self._resolve_decode(body)
        if sess is None:
            self._count("generate", model, 404)
            h.send_json(404, {
                "error": "unknown_model",
                "detail": f"no decode model {model!r}; available: "
                          f"{sorted(self._decode)}"})
            return
        stream = bool(body.get("stream"))
        kwargs = {}
        for k in ("max_new_tokens", "temperature", "seed", "eos_id",
                  "deadline_ms"):
            if body.get(k) is not None:
                kwargs[k] = body[k]
        if "deadline_ms" not in kwargs and \
                self.default_deadline_ms is not None:
            kwargs["deadline_ms"] = self.default_deadline_ms
        if not self._check_admittable(h, "generate", model):
            return
        try:
            # the request's trace lane roots HERE, at the socket — the
            # scheduler's submit/prefill/ride spans nest under the wire
            # (DecodeScheduler.submit hangs decode.submit off the context
            # active on this thread), and so does the first frame's egress
            ctx = _trace.start("gateway.request", route="generate",
                               model=str(model),
                               stream=stream) if _tel.enabled else None
            try:
                with _trace.use(ctx):
                    if stream:
                        src = sess.stream(body.get("prompt"), **kwargs)
                    else:
                        src = sess.submit(body.get("prompt"), **kwargs)
            except RequestRejected as e:
                self._shed(h, "generate", model, e, source=sess)
                return
            except (TypeError, ValueError) as e:
                self._count("generate", model, 400)
                self._bad_request(h, str(e))
                return
            if _tel.enabled:
                _tel.observe("gateway.queue_wait_ms",
                             (time.perf_counter() - t_wire) * 1e3)
            if stream:
                self._stream_response(h, model, src, t_wire, source=sess,
                                      ctx=ctx)
            else:
                self._buffered_response(h, model, src, t_wire, source=sess)
        finally:
            self.admission.release(model)

    def _buffered_response(self, h, model, future, t_wire, source=None):
        try:
            res = future.result()
        except RequestRejected as e:
            self._shed(h, "generate", model, e, source=source)
            return
        except Exception as e:     # noqa: BLE001 — a step failure is a 500
            self._count("generate", model, 500)
            h.send_json(500, {"error": "generation_failed",
                              "detail": repr(e)})
            return
        payload = {"model": model, "token_ids": res.token_ids,
                   "finish_reason": res.finish_reason,
                   "ttft_ms": res.ttft_ms, "latency_ms": res.latency_ms}
        if _tel.enabled:
            # buffered TTFT at the HTTP layer: the client sees its first
            # token only when the whole body lands
            _tel.observe("gateway.ttft_buffered_ms",
                         (time.perf_counter() - t_wire) * 1e3)
            _tel.observe("gateway.bytes_out",
                         float(len(json.dumps(payload)) + 1))
        self._count("generate", model, 200)
        h.send_json(200, payload)

    def _start_sse(self, h, model):
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-cache")
        h.send_header("Connection", "close")
        h.end_headers()
        h.close_connection = True
        self._count("generate", model, 200)

    def _client_hangup(self, sink):
        """The SSE reader vanished mid-stream: abort the session so its
        KV pages free at the next boundary instead of decoding an answer
        nobody will read (asserted: ``decode.evictions`` bumps with
        ``reason="aborted"``, zero leaked pages)."""
        sink.cancel()
        _flight.record("gateway.client_hangup")
        if _tel.enabled:
            _tel.count("gateway.client_disconnects", route="generate")

    def _finish_sse(self, h, final, bytes_out):
        try:
            for payload in (json.dumps(final), "[DONE]"):
                frame = f"data: {payload}\n\n".encode()
                h.wfile.write(frame)
                bytes_out += len(frame)
            h.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            return
        finally:
            if _tel.enabled:
                _tel.observe("gateway.bytes_out", float(bytes_out))

    def _stream_response(self, h, model, sink, t_wire, source=None,
                         ctx=None):
        self._start_sse(h, model)
        bytes_out = 0
        first = True
        final = None
        try:
            for i, tok in enumerate(sink):
                frame = ("data: " +
                         json.dumps({"token": tok, "index": i}) +
                         "\n\n").encode()
                h.wfile.write(frame)
                h.wfile.flush()
                bytes_out += len(frame)
                if first and _tel.enabled:
                    now = time.perf_counter()
                    _tel.observe("gateway.ttft_streamed_ms",
                                 (now - t_wire) * 1e3)
                    if ctx is not None:
                        # the hand-over from the scheduler's thread to
                        # this writer: first token put -> first frame
                        # flushed, on the request's own lane
                        _tel.record_span("gateway.first_frame",
                                         sink.t_first_put, now,
                                         tid=ctx.trace_id, trace=ctx)
                first = False
            res = sink.result()
            final = {"done": True, "finish_reason": res.finish_reason,
                     "ttft_ms": res.ttft_ms, "latency_ms": res.latency_ms,
                     "n_tokens": len(res.token_ids)}
        except (BrokenPipeError, ConnectionResetError):
            self._client_hangup(sink)
            return
        except RequestRejected as e:
            final = {"done": True, "error": e.reason, "detail": str(e)}
            if _tel.enabled:
                _tel.count("gateway.shed", route="generate",
                           reason=e.reason)
        except Exception as e:     # noqa: BLE001 — surfaced in-stream
            final = {"done": True, "error": "generation_failed",
                     "detail": repr(e)}
        self._finish_sse(h, final, bytes_out)

    # --------------------------------------------------------- proxy routes
    def _proxy_generate(self, h, body, t_wire):
        from ..fleet.transport import RemoteError
        model = body.get("model") or "default"
        stream = bool(body.get("stream"))
        params = {k: body[k] for k in
                  ("model", "prompt", "max_new_tokens", "temperature",
                   "seed", "eos_id") if body.get(k) is not None}
        deadline_ms = body.get("deadline_ms", self.default_deadline_ms)
        if not self._check_admittable(h, "generate", model):
            return
        try:
            ctx = _trace.start("gateway.request", route="generate",
                               model=str(model), proxy=True,
                               stream=stream) if _tel.enabled else None
            try:
                if stream:
                    src = self.owner.stream("generate", params,
                                            deadline_ms=deadline_ms,
                                            trace=ctx)
                else:
                    result = self.owner.call("generate", params,
                                             deadline_ms=deadline_ms,
                                             trace=ctx)
            except RequestRejected as e:
                self._shed(h, "generate", model, e)
                return
            except KeyError as e:
                self._count("generate", model, 404)
                h.send_json(404, {"error": "unknown_model",
                                  "detail": str(e)})
                return
            except (TypeError, ValueError) as e:
                self._count("generate", model, 400)
                self._bad_request(h, str(e))
                return
            except RemoteError as e:
                self._count("generate", model, 500)
                h.send_json(500, {"error": "generation_failed",
                                  "detail": e.detail})
                return
            except (OSError, TimeoutError) as e:
                # OwnerGone + failed dials land here: the owner is down
                self._owner_unavailable(h, "generate", model, e)
                return
            if stream:
                self._proxy_stream_response(h, model, src, t_wire)
            else:
                payload = dict(result, model=model)
                if _tel.enabled:
                    _tel.observe("gateway.ttft_buffered_ms",
                                 (time.perf_counter() - t_wire) * 1e3)
                self._count("generate", model, 200)
                h.send_json(200, payload)
        finally:
            self.admission.release(model)

    def _proxy_stream_response(self, h, model, src, t_wire):
        """SSE over a fleet :class:`ClientStream`.  The degradation
        contract: an owner crash mid-stream ends the stream with a
        terminal ``{"done": true, "error": "owner_restart"}`` frame and
        ``[DONE]`` — the client always sees a well-formed stream end,
        never a torn connection, never a 5xx."""
        from ..fleet.transport import OwnerGone, RemoteError
        self._start_sse(h, model)
        bytes_out = 0
        first = True
        final = None
        try:
            for payload in src:
                frame = ("data: " +
                         json.dumps({"token": payload.get("token"),
                                     "index": payload.get("index")}) +
                         "\n\n").encode()
                h.wfile.write(frame)
                h.wfile.flush()
                bytes_out += len(frame)
                if first and _tel.enabled:
                    _tel.observe("gateway.ttft_streamed_ms",
                                 (time.perf_counter() - t_wire) * 1e3)
                first = False
            res = src.result()
            final = {"done": True,
                     "finish_reason": res.get("finish_reason"),
                     "ttft_ms": res.get("ttft_ms"),
                     "latency_ms": res.get("latency_ms"),
                     "n_tokens": len(res.get("token_ids") or ())}
        # OwnerGone is a ConnectionError too — catch it BEFORE the
        # client-side BrokenPipe/Reset pair or a dead owner would be
        # mistaken for a hung-up client
        except (OwnerGone, TimeoutError) as e:
            final = {"done": True, "error": "owner_restart",
                     "detail": str(e) or repr(e)}
            if _tel.enabled:
                _tel.count("gateway.stream_owner_lost")
        except (BrokenPipeError, ConnectionResetError):
            # client hung up: tell the owner to abort the session (its
            # KV pages free at the next boundary)
            src.cancel()
            _flight.record("gateway.client_hangup")
            if _tel.enabled:
                _tel.count("gateway.client_disconnects", route="generate")
            return
        except RequestRejected as e:
            final = {"done": True, "error": e.reason, "detail": str(e)}
            if _tel.enabled:
                _tel.count("gateway.shed", route="generate",
                           reason=e.reason)
        except RemoteError as e:
            final = {"done": True, "error": "generation_failed",
                     "detail": e.detail}
        except Exception as e:     # noqa: BLE001 — surfaced in-stream
            final = {"done": True, "error": "generation_failed",
                     "detail": repr(e)}
        self._finish_sse(h, final, bytes_out)

    def _proxy_infer(self, h, body, t_wire):
        """Idempotent by construction (pure function of its inputs), so
        an owner crash mid-call is transparently retried against the
        supervisor's restarted owner — within the request's deadline (or
        the gateway's retry budget).  The client sees one slow 200, not
        an error it must handle."""
        from ..fleet.transport import RemoteError
        model = body.get("model") or "default"
        if body.get("inputs") is None:
            self._count("infer", model, 400)
            self._bad_request(h, "missing 'inputs'")
            return
        deadline_ms = body.get("deadline_ms", self.default_deadline_ms)
        if not self._check_admittable(h, "infer", model):
            return
        try:
            ctx = _trace.start("gateway.request", route="infer",
                               model=str(model),
                               proxy=True) if _tel.enabled else None
            params = {"model": body.get("model"), "inputs": body["inputs"],
                      "multi_input": bool(body.get("multi_input"))}
            budget_s = (deadline_ms / 1e3 if deadline_ms is not None
                        else self.infer_retry_budget_ms / 1e3)
            give_up = t_wire + budget_s
            attempt = 0
            while True:
                remaining_s = give_up - time.perf_counter()
                try:
                    out = self.owner.call("infer", params,
                                          deadline_ms=max(
                                              1.0, remaining_s * 1e3),
                                          trace=ctx)
                    break
                except RequestRejected as e:
                    self._shed(h, "infer", model, e)
                    return
                except KeyError as e:
                    self._count("infer", model, 404)
                    h.send_json(404, {"error": "unknown_model",
                                      "detail": str(e)})
                    return
                except (TypeError, ValueError) as e:
                    self._count("infer", model, 400)
                    self._bad_request(h, str(e))
                    return
                except RemoteError as e:
                    self._count("infer", model, 500)
                    h.send_json(500, {"error": "inference_failed",
                                      "detail": e.detail})
                    return
                except (OSError, TimeoutError) as e:
                    # the owner died under us; the supervisor is already
                    # restarting it — retry within the deadline, and
                    # only then degrade to 503
                    attempt += 1
                    if time.perf_counter() + 0.05 >= give_up or \
                            self._draining.is_set():
                        self._owner_unavailable(h, "infer", model, e)
                        return
                    if _tel.enabled:
                        _tel.count("gateway.infer_retries")
                    # the client's own reconnect policy backs off on
                    # dial; this only paces poll attempts between dials
                    time.sleep(min(0.05 * attempt, 0.5))
            if attempt and _tel.enabled:
                _tel.instant("gateway.infer_retried", attempts=attempt,
                             model=str(model))
            resp = {"model": model, "outputs": self._tolist(out)}
            if _tel.enabled:
                _tel.observe("gateway.bytes_out",
                             float(len(json.dumps(resp)) + 1))
            self._count("infer", model, 200)
            h.send_json(200, resp)
        finally:
            self.admission.release(model)

    @staticmethod
    def _tolist(out):
        if isinstance(out, (tuple, list)):
            return [np.asarray(o).tolist() for o in out]
        return np.asarray(out).tolist()

    # ------------------------------------------------------- POST /v1/infer
    def _route_infer(self, h):
        t_wire = time.perf_counter()
        body = self._parse(h)
        if body is None:
            return
        if self.owner is not None:
            self._proxy_infer(h, body, t_wire)
            return
        model = body.get("model")
        if self.registry is None or model is None or \
                model not in self.registry:
            self._count("infer", model, 404)
            avail = self.registry.names() if self.registry is not None \
                else []
            h.send_json(404, {"error": "unknown_model",
                              "detail": f"no model {model!r}; available: "
                                        f"{avail}"})
            return
        if body.get("inputs") is None:
            self._count("infer", model, 400)
            self._bad_request(h, "missing 'inputs'")
            return
        deadline_ms = body.get("deadline_ms", self.default_deadline_ms)
        if not self._check_admittable(h, "infer", model):
            return
        try:
            batcher = self.registry.get(model)
        except KeyError:
            batcher = None
        try:
            ctx = _trace.start("gateway.request", route="infer",
                               model=str(model)) if _tel.enabled else None
            inputs = body["inputs"]
            # multi-input models take {"multi_input": true, "inputs":
            # [in0, in1, ...]} — one array per model input
            payload = (tuple(np.asarray(x) for x in inputs)
                       if body.get("multi_input") else np.asarray(inputs))
            try:
                with _trace.use(ctx):
                    fut = self.registry.submit(model, payload,
                                               deadline_ms=deadline_ms)
            except RequestRejected as e:
                self._shed(h, "infer", model, e, source=batcher)
                return
            except (TypeError, ValueError) as e:
                self._count("infer", model, 400)
                self._bad_request(h, str(e))
                return
            if _tel.enabled:
                _tel.observe("gateway.queue_wait_ms",
                             (time.perf_counter() - t_wire) * 1e3)
            try:
                out = fut.result()
            except RequestRejected as e:
                self._shed(h, "infer", model, e, source=batcher)
                return
            except Exception as e:     # noqa: BLE001 — a batch bug is a 500
                self._count("infer", model, 500)
                h.send_json(500, {"error": "inference_failed",
                                  "detail": repr(e)})
                return
            resp = {"model": model, "outputs": self._tolist(out)}
            if _tel.enabled:
                _tel.observe("gateway.bytes_out",
                             float(len(json.dumps(resp)) + 1))
            self._count("infer", model, 200)
            h.send_json(200, resp)
        finally:
            self.admission.release(model)

    # ------------------------------------------------------------- shutdown
    def close(self):
        """Unmount the gateway's routes and probes.  The shared server
        stays up (telemetry owns it; its single atexit hook is the one
        shutdown path)."""
        if self._closed:
            return
        self._closed = True
        self._draining.set()
        for method, path, fn in self._mounts:
            _http.unregister_route(method, path, fn)
        _http.unregister_health(f"gateway:{self.name}", self)
        _http.unregister_ready(f"gateway:{self.name}", self)
        if self.owner is not None and self._owns_client:
            self.owner.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
