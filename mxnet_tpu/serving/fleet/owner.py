"""The device-owner process: chips, programs and KV cache behind RPC.

Exactly one process on the box owns the devices.  It hosts the
:class:`~mxnet_tpu.serving.ModelRegistry` (batched ``infer``) and the
decode sessions (continuous batching, paged KV), and serves them over
the :mod:`.transport` Unix-socket protocol.  Everything stateful and
crashable lives HERE — a model bug, an XLA assert, an OOM kills this
process and *only* this process; the supervisor restarts it (re-warming
bitwise-identically from the AOT :class:`~mxnet_tpu.serving.aot.
ProgramCache`) while the front-ends keep answering with honest 503s.

The models are built by a **builder spec** — ``"module:callable"`` —
because compiled runtimes cannot cross a process boundary; the child
imports the builder and constructs everything fresh.  Builder
signature::

    def build(aot_cache=None):
        return {"registry": ModelRegistry_or_None,
                "decode": {name: DecodeSession_or_Scheduler, ...}}

Run as a module (what the supervisor execs)::

    python -m mxnet_tpu.serving.fleet.owner \
        --spec tests.fleet_builder:build --socket /run/owner.sock \
        [--aot-cache DIR] [--generation N]

SIGTERM drains: stop taking new RPCs, finish in-flight decode/infer,
exit 0.  SIGKILL is the crash drill — the supervisor notices via
waitpid/heartbeats and respawns; KV slots, sockets and breaker state
die with the process, which is precisely the robustness contract (no
cross-process cleanup protocol to get wrong).
"""
from __future__ import annotations

import argparse
import importlib
import os
import signal
import sys
import threading
import time

import numpy as np

from ...telemetry import bus as _tel
from ...telemetry import flight as _flight
from ...telemetry import trace as _trace
from ..batcher import RequestRejected
from ..runtime import device_info
from .transport import RPCServer

__all__ = ["OwnerService", "load_builder", "serve", "main"]


def load_builder(spec):
    """``"pkg.mod:callable"`` -> the callable.  The separator is ``:``
    (an importable module path left of it), mirroring console-script
    entry-point syntax."""
    if ":" not in spec:
        raise ValueError(
            f"builder spec {spec!r} must look like 'pkg.module:callable'")
    mod_name, _, fn_name = spec.partition(":")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, fn_name, None)
    if not callable(fn):
        raise ValueError(f"{spec!r} does not name a callable")
    return fn


class OwnerService:
    """RPC method surface over one registry + named decode sessions.

    Methods (the ``method`` field of a REQ frame):

    - ``ping`` — also answered as a PONG frame without a method call.
    - ``infer`` — ``{model, inputs, multi_input?}`` through the
      registry's Batcher; numpy arrays ride the pickle frames natively.
    - ``generate`` — ``{model?, prompt, opts...}``; with ``stream=True``
      on the REQ, each token is emitted as a STREAM frame the step
      boundary it lands, and a CANCEL frame aborts the session (KV
      pages freed at the next boundary).
    - ``stats`` — per-session KV/queue stats + pid/generation, the
      leak-accounting surface the chaos drill asserts on.
    - ``drain`` — begin graceful shutdown (the SIGTERM path, callable
      remotely too).
    """

    def __init__(self, registry=None, decode=None, generation=0):
        self.registry = registry
        self.decode = dict(decode or {})
        self.generation = int(generation)
        self.started_at = time.time()
        self._draining = threading.Event()
        # this process IS the device owner: say which device it holds, so a
        # front-end (which never touches one) can report and assert it
        import jax
        self.device = device_info(jax.local_devices()[0])

    # ----------------------------------------------------------- dispatch
    def pong(self):
        return {"pid": os.getpid(), "generation": self.generation,
                "draining": self._draining.is_set(), **self.device}

    def handle(self, method, params, deadline_ms, trace, emit,
               register_cancel):
        if self._draining.is_set() and method not in ("stats", "drain"):
            raise RequestRejected("shutdown", "owner is draining")
        ctx = None
        if trace is not None and _tel.enabled:
            # the request's lane continues across the process boundary:
            # same trace id, the wire-side span as parent
            ctx = _trace.TraceContext(int(trace[0]), int(trace[1]))
        with _trace.use(ctx):
            if method == "ping":
                return self.pong()
            if method == "infer":
                return self._infer(params, deadline_ms)
            if method == "generate":
                return self._generate(params, deadline_ms, emit,
                                      register_cancel)
            if method == "stats":
                return self.stats()
            if method == "drain":
                self._draining.set()
                return {"draining": True}
        raise ValueError(f"unknown fleet method {method!r}")

    # ------------------------------------------------------------ methods
    def _infer(self, params, deadline_ms):
        if self.registry is None:
            raise KeyError("no registry in this owner")
        model = params.get("model")
        if model is None or model not in self.registry:
            raise KeyError(f"no model {model!r}; available: "
                           f"{self.registry.names()}")
        inputs = params.get("inputs")
        if inputs is None:
            raise ValueError("missing 'inputs'")
        payload = (tuple(np.asarray(x) for x in inputs)
                   if params.get("multi_input") else np.asarray(inputs))
        fut = self.registry.submit(model, payload, deadline_ms=deadline_ms)
        out = fut.result()
        if isinstance(out, tuple):
            return tuple(np.asarray(o) for o in out)
        return np.asarray(out)

    def _resolve_decode(self, params):
        name = params.get("model")
        if name is None and len(self.decode) == 1:
            name = next(iter(self.decode))
        sess = self.decode.get(name)
        if sess is None:
            raise KeyError(f"no decode model {name!r}; available: "
                           f"{sorted(self.decode)}")
        return name, sess

    def _generate(self, params, deadline_ms, emit, register_cancel):
        _name, sess = self._resolve_decode(params)
        kwargs = {}
        for k in ("max_new_tokens", "temperature", "seed", "eos_id"):
            if params.get(k) is not None:
                kwargs[k] = params[k]
        if deadline_ms is not None:
            kwargs["deadline_ms"] = deadline_ms
        prompt = params.get("prompt")
        if emit is None:
            res = sess.submit(prompt, **kwargs).result()
            return self._result_payload(res)
        sink = sess.stream(prompt, **kwargs)
        register_cancel(sink)
        for i, tok in enumerate(sink):
            emit({"token": int(tok), "index": i})
        res = sink.result()
        return self._result_payload(res)

    @staticmethod
    def _result_payload(res):
        return {"token_ids": list(res.token_ids),
                "finish_reason": res.finish_reason,
                "ttft_ms": res.ttft_ms, "latency_ms": res.latency_ms}

    def cancel(self, key):
        """CANCEL frame target: ``key`` is the TokenStream a streaming
        generate registered — aborts the session (queued or running)."""
        key.cancel()

    def stats(self):
        out = {"pid": os.getpid(), "generation": self.generation,
               "uptime_s": round(time.time() - self.started_at, 3),
               "draining": self._draining.is_set(), "decode": {},
               **self.device}
        for name, sess in self.decode.items():
            try:
                out["decode"][name] = sess.stats()
            except Exception as e:       # noqa: BLE001 — stats best-effort
                out["decode"][name] = {"error": repr(e)}
        if self.registry is not None:
            out["infer_models"] = self.registry.names()
        return out

    # ------------------------------------------------------------- drain
    @property
    def draining(self):
        return self._draining.is_set()

    def drain(self):
        self._draining.set()

    def close(self, drain=True):
        self._draining.set()
        for sess in self.decode.values():
            try:
                sess.close(drain=drain)
            except Exception:            # noqa: BLE001 — teardown sweep
                pass
        if self.registry is not None:
            try:
                self.registry.close(drain=drain)
            except Exception:            # noqa: BLE001 — teardown sweep
                pass


def serve(spec, socket_path, aot_cache=None, generation=0,
          ready_fd=None):
    """Build the models, serve RPC, block until drained.  The body of
    the owner process (also callable in-process for tests).

    ``ready_fd``: optional pipe fd; one byte is written when the socket
    is accepting — the spawner's readiness signal that never races the
    first heartbeat."""
    builder = load_builder(spec)
    t0 = time.perf_counter()
    built = builder(aot_cache=aot_cache)
    warm_s = time.perf_counter() - t0
    service = OwnerService(registry=built.get("registry"),
                           decode=built.get("decode"),
                           generation=generation)
    server = RPCServer(socket_path, service)
    _flight.record("fleet.owner_up", value=int(generation))
    if _tel.enabled:
        _tel.count("fleet.owner_warm_ms", round(warm_s * 1e3, 3))
        _tel.gauge("fleet.owner_generation", int(generation))

    stop = threading.Event()

    def _sigterm(signum, frame):
        # drain, don't drop: stop admitting, finish in-flight, exit 0
        service.drain()
        stop.set()

    try:
        signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass          # not the main thread (in-process test harness)
    if ready_fd is not None:
        os.write(ready_fd, b"R")
        os.close(ready_fd)
    try:
        while not stop.is_set():
            stop.wait(0.2)
    finally:
        service.close(drain=True)
        server.close()
        _flight.record("fleet.owner_exit", value=int(generation))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--spec", required=True,
                   help="model builder, 'pkg.module:callable'")
    p.add_argument("--socket", required=True, help="unix socket path")
    p.add_argument("--aot-cache", default=None,
                   help="persistent AOT program cache dir (warm restarts)")
    p.add_argument("--generation", type=int, default=0,
                   help="supervisor restart counter (telemetry label)")
    p.add_argument("--ready-fd", type=int, default=None,
                   help="fd to write one byte to once serving")
    args = p.parse_args(argv)
    # a restart is only warm if every incarnation finds what the last one
    # compiled: JAX's persistent cache, placed from outside (or at its one
    # fixed path), beside the AOT program cache
    from ...runtime import compile_cache
    compile_cache()
    return serve(args.spec, args.socket, aot_cache=args.aot_cache,
                 generation=args.generation, ready_fd=args.ready_fd)


if __name__ == "__main__":
    sys.exit(main())
