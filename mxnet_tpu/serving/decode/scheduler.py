"""Continuous-batching decode scheduler + the ``generate()`` front-end.

Static batching runs a gang of requests start-to-finish: the batch drains
as its slowest member finishes and new arrivals wait for the whole gang.
**Continuous batching** admits requests into the *running* decode batch at
step boundaries and evicts finished sequences immediately, freeing their
KV pages for the next arrival — the device never idles while work is
queued, which is where the tokens/sec win at mixed prompt lengths comes
from.

The request plane carries over the PR 3 ``Batcher`` contract wholesale —
bounded queue with backpressure, per-request deadlines with load shedding,
circuit breaker after consecutive batch failures — plus one new shed
condition: **KV-cache exhaustion**.  A request whose page reservation can
*never* fit is rejected immediately (``reason="kv_exhausted"``); one that
merely can't fit *right now* waits for evictions (its deadline still
applies).  Admission reserves the full ``prompt + max_new_tokens`` page
budget, so an admitted sequence can always run to completion — mid-flight
eviction-for-space never happens.

Determinism: a request's token stream is a pure function of (prompt, seed,
temperature) — per-request PRNG keys fold the *request-local* token index,
and the runtime's row-stable math keeps every step bitwise-independent of
batch composition — so the same request returns bitwise-identical tokens
solo or inside any continuous batch (tested, and the property that makes
"replay this request" a debugging tool).

Fault sites: ``decode.step`` fires inside the per-step try, before a step's
launch (an injected fault fails the active requests, the rows of the step
still in flight among them, and frees their slots — the mid-decode crash
drill), ``decode.kv_alloc`` inside the cache allocator.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import CancelledError, Future, InvalidStateError

import numpy as np

from ...analysis import sanitizer as _san
from ...resilience import faults as _faults
from ...telemetry import bus as _tel
from ...telemetry import flight as _flight
from ...telemetry import http as _http
from ...telemetry import trace as _trace
from ..batcher import RequestRejected
from ..runtime import device_info
from .kv_cache import KVCacheExhausted, pages_needed
from .runtime import DecodeRuntime
from .speculate import SpecState, resolve_drafter

__all__ = ["DecodeScheduler", "DecodeSession", "GenerationResult",
           "TokenStream"]

_NO_DRAFT = np.zeros((0,), "int32")


class TokenStream:
    """Incremental per-request token feed — the streaming (SSE) view of
    one generation.  Iterating yields token ids the moment the producing
    step boundary commits them; iteration ends when the request finishes
    (the :class:`GenerationResult` is then available via :meth:`result`)
    and re-raises the request's error if it was rejected, failed, or
    cancelled.

    The stream is an *observer*, not a fork: a request submitted with a
    sink appends to the very same token list and resolves the very same
    Future as a buffered one, and the per-request PRNG fold-in never sees
    the sink — so the streamed and buffered token sequences are
    bitwise-identical by construction (CI asserts it end-to-end over
    HTTP)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._pending = deque()
        self._done = False
        self._result = None
        self._exc = None
        self._future = None       # attached by stream()/submit's caller
        self._abort = None        # scheduler abort hook for running requests
        # perf_counter of the first _put: where the scheduler thread handed
        # the first token over (the gateway's first-frame span starts here)
        self.t_first_put = None

    # ------------------------------- producer (scheduler worker thread)
    def _put(self, token):
        if self.t_first_put is None:
            self.t_first_put = time.perf_counter()
        with self._cond:
            self._pending.append(int(token))
            self._cond.notify_all()

    def _finish(self, result):
        with self._cond:
            if not self._done:
                self._result = result
                self._done = True
                self._cond.notify_all()

    def _fail(self, exc):
        with self._cond:
            if not self._done:
                self._exc = exc
                self._done = True
                self._cond.notify_all()

    # ---------------------------------------------------------- consumer
    def next_token(self, timeout=None):
        """Block for the next token id.  Raises ``StopIteration`` at end
        of stream, the request's error on failure, ``TimeoutError`` when
        nothing arrives in time."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._cond:
            while True:
                if self._pending:
                    return self._pending.popleft()
                if self._done:
                    if self._exc is not None:
                        raise self._exc
                    raise StopIteration
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"no token within {timeout:.3f}s")
                self._cond.wait(timeout=remaining)

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_token()

    def result(self, timeout=None):
        """The finished request's :class:`GenerationResult` (blocks until
        the request completes; tokens stay iterable — result() drains
        nothing)."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._cond:
            while not self._done:
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("request still running")
                self._cond.wait(timeout=remaining)
            if self._exc is not None:
                raise self._exc
            return self._result

    @property
    def done(self):
        with self._cond:
            return self._done

    def cancel(self):
        """Best-effort cancel of the underlying request.  While queued
        the Future cancels outright; once running, the scheduler aborts
        the request at the next step boundary — the slot is evicted with
        ``reason="aborted"`` and every KV page freed (the
        client-hung-up-mid-stream path: decoding to completion for a
        departed reader would burn batch rows for nobody)."""
        cancelled = self._future.cancel() \
            if self._future is not None else False
        if not cancelled and self._abort is not None and not self.done:
            self._abort()
            return True
        return cancelled


class GenerationResult:
    """One finished request: generated ``token_ids`` (prompt excluded),
    ``finish_reason`` (``"eos"`` / ``"length"``), time-to-first-token and
    end-to-end latency in ms."""

    __slots__ = ("token_ids", "finish_reason", "ttft_ms", "latency_ms",
                 "prompt_len")

    def __init__(self, token_ids, finish_reason, ttft_ms, latency_ms,
                 prompt_len):
        self.token_ids = list(token_ids)
        self.finish_reason = finish_reason
        self.ttft_ms = ttft_ms
        self.latency_ms = latency_ms
        self.prompt_len = prompt_len

    def __repr__(self):
        return (f"GenerationResult({len(self.token_ids)} tokens, "
                f"{self.finish_reason!r}, ttft={self.ttft_ms:.1f}ms)")


class _Request:
    __slots__ = ("prompt", "max_new", "temp", "key", "eos_id", "deadline",
                 "future", "t_submit", "n_pages", "slot", "tokens",
                 "position", "step_idx", "cur", "ttft_ms", "ctx", "lane",
                 "sink", "aborted", "spec", "spec_state")

    def __init__(self, prompt, max_new, temp, key, eos_id, deadline,
                 t_submit, n_pages):
        self.prompt = prompt
        self.max_new = max_new
        self.temp = temp
        self.key = key                    # (2,) uint32 request base key
        self.eos_id = eos_id
        self.deadline = deadline
        self.future = Future()
        self.t_submit = t_submit
        self.n_pages = n_pages
        self.slot = None                  # KVSlot once admitted
        self.tokens = []                  # generated ids
        self.position = len(prompt)       # next write position
        self.step_idx = 0                 # per-request sampling step
        self.cur = 0                      # last sampled token (step input)
        self.ttft_ms = None
        # ctx: trace context minted at submit (None with telemetry off).
        # lane: the request's own chrome-trace thread lane (the trace id)
        # — queue wait, prefill, every ride and the eviction land there,
        # so one request reads as one horizontal track in Perfetto.
        self.ctx = None
        self.lane = None
        # sink: TokenStream observing this request (None for buffered
        # submits) — fed at exactly the points tokens land in `tokens`
        self.sink = None
        # aborted: client hung up / cancelled a RUNNING request; swept
        # out of the batch (slot freed) at the next step boundary
        self.aborted = False
        # spec: this request rides the speculative verify path (degrades
        # to False if the drafter fails to attach); spec_state carries
        # the adaptive per-request spec_k + acceptance window
        self.spec = False
        self.spec_state = None


class _Flying:
    """The plain step in flight: the runtime's ``step`` (a ``StepFlight``)
    and its ``rows``, the request in each place of the batch (None: a place
    nobody rides)."""

    __slots__ = ("step", "rows")

    def __init__(self, step, rows):
        self.step = step
        self.rows = rows


class DecodeScheduler:
    """Worker thread running the continuous decode loop for one
    :class:`DecodeRuntime` (see module docstring for the contract).

    Parameters
    ----------
    runtime : DecodeRuntime
    queue_depth : int
        Bound on *queued* (not yet admitted) requests; beyond it
        ``submit()`` blocks (backpressure) or sheds on deadline expiry.
    start : bool
        Start the worker now (default); ``start=False`` lets tests
        enqueue deterministically.
    breaker_threshold / breaker_cooldown_ms
        Circuit breaker on consecutive prefill/step failures (None
        disables) — same semantics as ``serving.Batcher``.
    drafter : Drafter | "ngram" | None
        Enables speculative decoding: requests ride the fused verify
        program with this drafter's proposals (the runtime must have
        been built with ``spec_buckets``).  Output streams stay bitwise
        identical to non-speculative decode — acceptance is
        deterministic-equality against the target's own fold_in sample
        stream, so the drafter only ever changes tokens *per step*.
    spec_k : int | None
        Initial per-request draft length (adapts within
        ``[1, runtime.max_spec_k]`` from each request's windowed
        acceptance rate); default: the runtime's largest spec bucket.
    """

    def __init__(self, runtime, queue_depth=256, start=True,
                 breaker_threshold=8, breaker_cooldown_ms=1000.0,
                 drafter=None, spec_k=None):
        if not isinstance(runtime, DecodeRuntime):
            raise TypeError(f"need a DecodeRuntime, got {type(runtime)}")
        self._runtime = runtime
        self._cache = runtime.cache
        self._drafter = resolve_drafter(drafter)
        if self._drafter is not None and not runtime.spec_buckets:
            raise ValueError(
                "speculative decoding needs a runtime built with "
                "spec_buckets (the verify-program ladder); got none")
        self._spec_k0 = runtime.max_spec_k if spec_k is None \
            else int(spec_k)
        if self._drafter is not None and not \
                (1 <= self._spec_k0 <= runtime.max_spec_k):
            raise ValueError(
                f"spec_k must be in [1, {runtime.max_spec_k}], "
                f"got {self._spec_k0}")
        if self._drafter is not None:
            self._drafter.bind(runtime)
        if int(queue_depth) < 1:
            raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
        self.queue_depth = int(queue_depth)
        self._queue = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._closed = False
        self._drain = True
        self._started = False
        self._worker = None
        self._active = []                 # worker-thread-owned
        self._flying = None               # the plain step in flight, or None
        self.steps_failed = 0
        self.worker_restarts = 0
        if breaker_threshold is not None and int(breaker_threshold) < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1 or None, "
                f"got {breaker_threshold}")
        self._breaker_threshold = None if breaker_threshold is None \
            else int(breaker_threshold)
        self._breaker_cooldown = float(breaker_cooldown_ms) / 1e3
        self._consecutive_failures = 0
        self._breaker_open_until = 0.0
        # readiness surface: /readyz flips the moment the breaker opens
        # (liveness /healthz is for process-level probes — an open
        # breaker means "route traffic away", not "restart me")
        _http.register_ready(f"decode:{runtime.name}", self)
        if start:
            self.start()

    # --------------------------------------------------------------- client
    def submit(self, prompt, max_new_tokens=16, temperature=0.0, seed=0,
               eos_id=None, deadline_ms=None, sink=None, speculate=None):
        """Enqueue one generation request; returns a Future resolving to a
        :class:`GenerationResult`.

        ``speculate`` opts one request in/out of the speculative verify
        path (default: speculate iff the scheduler has a drafter).  The
        token stream is bitwise-identical either way — speculation only
        changes how many tokens each step commits.

        Malformed requests (empty prompt, out-of-range ids, a prompt +
        budget that overflows the context window) raise synchronously.  A
        reservation larger than the whole KV cache is shed immediately
        with ``reason="kv_exhausted"`` — it could never be admitted.

        ``sink`` (a :class:`TokenStream`) observes the request
        incrementally: each token is pushed at the step boundary that
        produced it, and the sink terminates with the same result or
        error the Future resolves with.  The sink changes NOTHING about
        scheduling or sampling — the buffered token stream stays
        bitwise-identical."""
        t_submit = time.perf_counter()
        rt = self._runtime
        prompt = np.asarray(prompt, "int32").reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size > rt.max_prompt_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds the largest seq "
                f"bucket ({rt.max_prompt_len})")
        vocab = rt.block.vocab_size
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise ValueError(f"prompt ids outside [0, {vocab})")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        ctx = self._cache.context_length
        if prompt.size + max_new > ctx:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds the context window ({ctx})")
        n_pages = pages_needed(prompt.size, max_new, self._cache.page_tokens)
        # request base key: any deterministic uint32 pair works (the step
        # program folds the per-request token index into it); derived in
        # numpy so submit() never touches the jax dispatch path
        seed = int(seed) & 0xffffffffffffffff
        key = np.array([seed >> 32, seed & 0xffffffff], "uint32")
        deadline = (t_submit + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        if speculate and self._drafter is None:
            raise ValueError(
                "speculate=True but the scheduler has no drafter")
        req = _Request(prompt, max_new, float(temperature), key,
                       eos_id, deadline, t_submit, n_pages)
        req.spec = (self._drafter is not None if speculate is None
                    else bool(speculate))
        if req.spec:
            req.spec_state = SpecState(self._spec_k0,
                                       self._runtime.max_spec_k)
        req.sink = sink
        if sink is not None:
            # the sink's cancel() reaches back here once the request is
            # RUNNING (Future.cancel no longer can): flag it for the
            # worker's boundary sweep
            sink._abort = lambda: self._abort_request(req)
        if _tel.enabled:
            # the request's trace: a child of the context active on the
            # caller's thread (the gateway's wire-side root, a fleet
            # owner's hop), a root of its own when there is none.  Its
            # lane carries every hop from here to eviction (admission,
            # prefill, each ride)
            parent = _trace.current()
            if parent is None:
                req.ctx = _trace.start("decode.submit", model=rt.name,
                                       prompt_len=int(prompt.size),
                                       max_new=max_new)
            else:
                link = _trace.child(parent)
                _tel.instant("decode.submit", trace=link, model=rt.name,
                             prompt_len=int(prompt.size), max_new=max_new)
                req.ctx = _trace.TraceContext(link[0], link[1])
            req.lane = req.ctx.trace_id
        with self._lock:
            if self._closed:
                self._reject(req, "shutdown", "scheduler is closed")
                raise req.future.exception()
            if self._breaker_open_until and \
                    time.perf_counter() < self._breaker_open_until:
                self._reject(
                    req, "unhealthy",
                    f"circuit breaker open after "
                    f"{self._consecutive_failures} consecutive failures")
                raise req.future.exception()
            if not self._cache.fits_ever(n_pages):
                self._reject(
                    req, "kv_exhausted",
                    f"reservation of {n_pages} pages can never fit "
                    f"({self._cache.usable_pages} usable, "
                    f"{self._cache.reclaimable_pages()} reclaimable from "
                    f"the shared-prefix cache)")
                raise req.future.exception()
            if self._started:
                self._respawn_worker_locked()
            while len(self._queue) >= self.queue_depth:
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    self._reject(req, "deadline",
                                 "queue stayed full past the deadline")
                    raise req.future.exception()
                self._not_full.wait(timeout=remaining)
                if self._closed:
                    self._reject(req, "shutdown", "scheduler is closed")
                    raise req.future.exception()
            self._queue.append(req)
            if _tel.enabled:
                _tel.count("decode.requests", model=self._runtime.name)
                _tel.gauge("decode.queue_depth", len(self._queue),
                           model=self._runtime.name)
            self._not_empty.notify()
        return req.future

    def generate(self, prompt, timeout=None, **kwargs):
        """Synchronous convenience: ``submit(...).result()``."""
        return self.submit(prompt, **kwargs).result(timeout)

    def stream(self, prompt, **kwargs):
        """Submit and return a :class:`TokenStream` yielding token ids as
        each step boundary commits them — the SSE data source.  Raises
        synchronously exactly like :meth:`submit` (malformed request,
        breaker open, impossible reservation)."""
        sink = TokenStream()
        future = self.submit(prompt, sink=sink, **kwargs)
        sink._future = future
        return sink

    def pending(self):
        with self._lock:
            return len(self._queue)

    def _abort_request(self, req):
        """Mark a running request for eviction at the next boundary (the
        worker owns the batch; this thread only raises the flag)."""
        with self._lock:
            req.aborted = True
            self._not_empty.notify()

    def active(self):
        """Sequences currently in the decode batch (approximate — read
        without joining the step boundary)."""
        return len(self._active)

    @property
    def device(self):
        """The ``jax.Device`` this scheduler's runtime runs on."""
        return self._runtime.device

    @property
    def healthy(self):
        if self._closed:
            return False
        if self._breaker_open_until and \
                time.perf_counter() < self._breaker_open_until:
            return False
        return True

    @property
    def breaker_remaining_s(self):
        """Seconds until an open circuit breaker lets traffic probe
        again (0.0 when closed) — the honest ``Retry-After`` value for
        ``reason="unhealthy"`` sheds."""
        return max(0.0, self._breaker_open_until - time.perf_counter())

    def _reject(self, req, reason, detail):
        if _tel.enabled:
            _tel.count("decode.rejections", model=self._runtime.name,
                       reason=reason)
            _tel.instant("decode.rejection", model=self._runtime.name,
                         reason=reason)
        exc = RequestRejected(reason, detail)
        try:
            req.future.set_exception(exc)
        except InvalidStateError:
            pass       # client cancel() won the race; nobody is waiting
        if req.sink is not None:
            req.sink._fail(exc)

    # --------------------------------------------------------------- worker
    def start(self):
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            self._started = True
            self._respawn_worker_locked()

    def _respawn_worker_locked(self):
        if self._worker is None or not self._worker.is_alive():
            if self._worker is not None:
                self.worker_restarts += 1
                if _tel.enabled:
                    _tel.count("decode.worker_restart",
                               model=self._runtime.name)
            self._worker = threading.Thread(
                target=self._run, daemon=True,
                name=f"decode-scheduler-{self._runtime.name}")
            self._worker.start()

    def _run(self):
        while self._turn():
            pass
        with self._lock:
            self._not_full.notify_all()

    def _turn(self):
        """One turn of the worker's loop: wait for work, run one boundary.
        False once the scheduler has shut down.  Each turn is one trace
        root, so its phase spans (``decode.idle``, ``decode.boundary`` and
        everything under it) carry ``parent_id`` and self times add up."""
        with _trace.use(_trace.start() if _tel.enabled else None):
            with self._lock:
                if not self._queue and not self._running():
                    with _tel.span("decode.idle"):
                        while not self._queue and not self._running():
                            if self._closed:
                                return False
                            self._not_empty.wait()
                if self._closed and not self._drain:
                    self._abort_locked()
                    return False
            self._boundary()
            with self._lock:
                if self._closed and not self._running() and \
                        (not self._drain or not self._queue):
                    self._shed_queue_locked("shutdown")
                    return False
        return True

    def _running(self):
        """Whether the batch still asks for a turn: a request holds a slot,
        or a step is in flight (its last rows may have left already)."""
        return bool(self._active) or self._flying is not None

    def _boundary(self):
        """One step boundary — admit under the lock, then prefill the
        joins and step the batch outside it.  The ONE body both the live
        worker and ``close()``'s inline settle run, so the two paths can
        never diverge."""
        # the turn reads this thread's and the process's CPU clocks, once:
        # cpu_ms over the turn's seconds is the loop's own share of a core,
        # proc_cpu_ms - cpu_ms what every OTHER thread (the door's writers,
        # clients in the process, the runtime's own) burned meanwhile
        with _tel.span("decode.boundary", cpu="process",
                       active=len(self._active)) as boundary:
            # admission, the lock's wait included
            with _tel.span("decode.admit",
                           queued=len(self._queue)) as admit:
                self._sweep_aborted()
                with self._lock:
                    joining = self._admit_locked()
                    self._not_full.notify_all()
                    if _tel.enabled:
                        _tel.gauge("decode.queue_depth", len(self._queue),
                                   model=self._runtime.name)
                admit.set(admitted=len(joining))
            boundary.set(joining=len(joining))
            try:
                if joining:
                    # behind the step in flight, which is collected after
                    # it: a joining prompt's launch hides behind that step
                    # as a decode step's does
                    self._prefill(joining)
                if self._running():
                    self._step()
            except BaseException as e:
                self._fail_active(e, joining)

    def _sweep_aborted(self):
        """Evict requests whose client gave up (stream cancel / hung-up
        SSE reader) before spending another step on them.  Runs on the
        worker thread at the boundary, before admission — the freed
        pages are allocatable in the same boundary."""
        if not any(req.aborted for req in self._active):
            return
        still = []
        for req in self._active:
            if not req.aborted:
                still.append(req)
                continue
            self._evict(req, "aborted")
            exc = CancelledError()
            if not req.future.done():
                req.future.set_exception(exc)
            if req.sink is not None:
                req.sink._fail(exc)
        self._active = still

    def _abort_locked(self):
        """Non-drain shutdown: shed the queue, fail the active batch,
        free every slot."""
        self._shed_queue_locked("shutdown")
        for req in self._active:
            self._evict(req, "shutdown")
            exc = RequestRejected("shutdown", "scheduler closed")
            if not req.future.done():
                req.future.set_exception(exc)
            if req.sink is not None:
                req.sink._fail(exc)
        self._active = []
        self._flying = None

    def _shed_queue_locked(self, reason):
        while self._queue:
            self._reject(self._queue.popleft(), reason,
                         "scheduler closed without drain")

    def _admit_locked(self):
        """Move queued requests into the batch at this step boundary:
        shed expired deadlines, then admit in arrival order while a KV
        reservation and a batch-bucket row are available.  Called under
        the lock; cache alloc/free only ever happens on this worker
        thread."""
        # deadline shedding sweeps the whole queue: a request behind a
        # too-big head must not rot past its deadline unobserved
        alive = deque()
        now = time.perf_counter()
        for req in self._queue:
            if req.future.cancelled():
                # never entered the batch, held no slot: not an eviction
                # — the request simply vanishes (its stream, if any,
                # still has to terminate)
                if req.sink is not None:
                    req.sink._fail(CancelledError())
            elif req.deadline is not None and now > req.deadline:
                self._reject(req, "deadline",
                             "expired waiting for admission")
            else:
                alive.append(req)
        self._queue = alive
        joining = []
        was_running = bool(self._active)
        while self._queue and \
                len(self._active) + len(joining) < self._runtime.max_batch:
            req = self._queue[0]
            try:
                # the prompt rides along: matched published prefix pages
                # are acquired by refcount (and a full-prompt hit carries
                # cached first-token logits) instead of allocated cold
                req.slot = self._cache.alloc(req.n_pages,
                                             prompt=req.prompt)
            except KVCacheExhausted:
                break        # wait for evictions; deadline still applies
            except Exception as e:
                # injected decode.kv_alloc fault (or a real allocator
                # error): fail THIS request, keep the scheduler alive
                self._queue.popleft()
                self._evict(req, "failed")
                try:
                    req.future.set_exception(e)
                except InvalidStateError:
                    pass      # client cancel() won the race
                if req.sink is not None:
                    req.sink._fail(e)
                continue
            self._queue.popleft()
            # claim the future BEFORE it enters the batch: once RUNNING, a
            # client cancel() can no longer race _finish's set_result (the
            # Batcher discipline); a cancel that won the race releases the
            # just-reserved slot here
            if not req.future.set_running_or_notify_cancel():
                self._evict(req, "cancelled")
                if req.sink is not None:
                    req.sink._fail(CancelledError())
                continue
            joining.append(req)
        if joining and _tel.enabled and was_running:
            _tel.count("decode.joins", len(joining),
                       model=self._runtime.name)
        return joining

    # ------------------------------------------------------------ decode ops
    def _prefill(self, joining):
        """Prefill admitted requests grouped by seq bucket, each group
        padded to a (batch, seq) grid point.  Requests whose whole prompt
        matched a published prefix never enter a group: their K/V is
        already paged in and the cached logits yield the first token —
        the prefix-hit TTFT path."""
        rt = self._runtime
        groups = {}
        for req in joining:
            if req.spec:
                # a failing drafter degrades the request to plain decode
                # (bitwise the same stream, just one token per step) —
                # drafts are never worth failing a request over
                try:
                    self._drafter.attach(req)
                except Exception as e:
                    req.spec = False
                    _flight.record("decode.spec_degraded",
                                   detail=f"{rt.name}: {e!r}")
                    if _tel.enabled:
                        _tel.count("decode.spec_degraded", model=rt.name)
        for req in joining:
            if req.slot.prefix_logits is not None:
                self._admit_prefix_hit(req)
            else:
                groups.setdefault(rt.seq_bucket_for(req.prompt.size),
                                  []).append(req)
        for s, reqs in sorted(groups.items()):
            for i in range(0, len(reqs), rt.max_prefill_batch):
                self._prefill_group(reqs[i:i + rt.max_prefill_batch], s)

    def _admit_prefix_hit(self, req):
        """A full-prompt prefix hit: admission IS the time-to-first-token
        — one batch-1 sample over the cached last-position logits (row-
        stable, so the token is bitwise what a cold prefill would have
        sampled), no prefill program, no K/V recompute."""
        rt = self._runtime
        _flight.record("decode.prefix_hit", detail=rt.name)
        t_pre = time.perf_counter()
        first = rt.sample_first(req.slot.prefix_logits, req.key, req.temp)
        req.slot.prefix_logits = None
        now = time.perf_counter()
        req.ttft_ms = (now - req.t_submit) * 1e3
        if _tel.enabled:
            _tel.observe("decode.ttft_ms", req.ttft_ms)
            _tel.count("decode.tokens", 1, model=rt.name)
            _tel.count("decode.prefill_skips", model=rt.name)
            if req.ctx is not None:
                _tel.record_span("decode.queue_wait", req.t_submit, t_pre,
                                 tid=req.lane, trace=req.ctx, model=rt.name)
                _tel.record_span("decode.prefix_hit", t_pre, now,
                                 tid=req.lane, trace=req.ctx, model=rt.name)
        req.cur = first
        req.tokens.append(first)
        if req.sink is not None:
            req.sink._put(first)
        req.step_idx = 1
        if self._is_finished(req):
            self._finish(req)
        else:
            self._active.append(req)
        self._consecutive_failures = 0

    def _prefill_group(self, reqs, s):
        rt, cache = self._runtime, self._cache
        with _tel.span("decode.prefill.prepare", rows=len(reqs),
                       seq_bucket=int(s)):
            b = rt.batch_bucket_for(len(reqs))
            tokens = np.zeros((b, s), "int32")
            lengths = np.ones((b,), "int32")
            tables = np.zeros((b, cache.table_width), "int32")
            keys = np.zeros((b, 2), "uint32")
            temps = np.zeros((b,), "float32")
            for r, req in enumerate(reqs):
                tokens[r, :req.prompt.size] = req.prompt
                lengths[r] = req.prompt.size
                # write_table: a partial prefix hit re-runs the full dense
                # prefill (bitwise the cold computation) but masks its
                # shared pages to the trash page at commit — their content
                # is already paged in and possibly read by live sequences
                tables[r] = req.slot.write_table()
                keys[r] = req.key
                temps[r] = req.temp
            _flight.record("decode.prefill", detail=rt.name,
                           value=len(reqs))
        t_pre = time.perf_counter()
        first, logits = rt.prefill(tokens, lengths, tables, keys, temps)
        with _tel.span("decode.prefill.fanout", rows=len(reqs),
                       seq_bucket=int(s)):
            if logits is not None:
                # publish BEFORE any decode step: each slot's prompt pages
                # hold exactly the prompt K/V right now (generated tokens
                # land later), so the index copies/pins clean pages
                for r, req in enumerate(reqs):
                    cache.publish(req.slot, req.prompt, logits[r])
            now = time.perf_counter()
            done = []
            for r, req in enumerate(reqs):
                req.ttft_ms = (now - req.t_submit) * 1e3
                if _tel.enabled:
                    _tel.observe("decode.ttft_ms", req.ttft_ms)
                    if req.ctx is not None:
                        # the request's own lane: time queued, then the
                        # prefill bucket it rode — both linked to its root
                        _tel.record_span("decode.queue_wait", req.t_submit,
                                         t_pre, tid=req.lane, trace=req.ctx,
                                         model=rt.name)
                        _tel.record_span("decode.ride_prefill", t_pre, now,
                                         tid=req.lane, trace=req.ctx,
                                         model=rt.name, seq_bucket=int(s),
                                         batch_bucket=int(b))
                req.cur = int(first[r])
                req.tokens.append(req.cur)
                if req.sink is not None:
                    req.sink._put(req.cur)
                req.step_idx = 1
                if self._is_finished(req):
                    done.append(req)
                else:
                    self._active.append(req)
            if _tel.enabled:
                _tel.count("decode.tokens", len(reqs), model=rt.name)
                _tel.count("decode.prefills", len(reqs), model=rt.name)
            for req in done:
                self._finish(req)
        self._consecutive_failures = 0

    def _step(self):
        """The active batch's turn at the device.  Injectable mid-decode
        crash: ``decode.step``.

        **A pipeline of depth one over consecutive plain steps.**  While
        the rows of the next step are the rows of the step in flight
        (:meth:`_rows_ahead`), step n+1 is launched BEFORE step n's tokens
        are collected: everything but the tokens is known to the host
        (positions, sampling indices, page tables: every page was reserved
        at admission), and the tokens go from one program to the next on
        the device.  The launch and this thread's wake then run behind the
        chip's work and not between its steps.  A row that reaches its
        ``max_new`` is known ahead and is simply not in step n+1 (its place
        rides as a padded row).  A row that ends by ``eos_id`` is known one
        step late: it rides step n+1 on its own reserved page, that token
        is dropped, and its slot is freed behind that launch (the device
        orders every later writer behind it).  Where the rows change in a
        way the device's tokens cannot follow (a join, whose first token is
        on the host; a smaller program that would do; a row that
        speculates), the step in flight is collected and fanned out first
        (:meth:`_land`) and this step is built from the host's tokens.

        With a drafter bound, boundaries where at least one active row
        produced a draft ride the fused verify program instead
        (:meth:`_spec_step`), never ahead of anything: a row's accepted
        count decides its next input.  Non-speculating rows ride along
        with ``n_draft = 0``, which is bitwise the plain step for them."""
        rt, cache = self._runtime, self._cache
        rows = self._rows_ahead()
        if rows is None:
            self._land()
            rows = list(self._active)
            if not rows:
                return
        flying = self._flying
        live = [req for req in rows if req is not None]
        n = len(live)
        b = rt.batch_bucket_for(len(rows))
        with _tel.span("decode.step.prepare", rows=n, batch_bucket=b):
            if _faults.active:
                _faults.check("decode.step")
            if _san.slots:
                for req in live:
                    cache.check_slot(req.slot)
            drafts = self._collect_drafts() if flying is None else None
            if drafts is None:
                args = self._step_args(
                    rows, b, None if flying is None else flying.step.tokens)
        if drafts is not None:
            self._spec_step(drafts)
            return
        _flight.record("decode.step", detail=rt.name, value=n)
        t0 = time.perf_counter()
        # the runtime's call of one turn, under the one span its readers
        # know: the launch of this step, the collect of the one before it
        with _tel.span("decode.step", model=rt.name, batch=b):
            self._flying = _Flying(rt.launch(*args), rows)
            if flying is not None and _tel.enabled:
                _tel.count("decode.steps_ahead", model=rt.name)
            # a launched step is taken: whatever is built next starts from it
            for req in live:
                req.position += 1
                req.step_idx += 1
            nxt = None if flying is None else rt.collect(flying.step)
        if flying is not None:
            self._fanout(flying, nxt, t0, time.perf_counter())

    def _rows_ahead(self):
        """The rows of a step that may be launched behind the one in
        flight, in that step's places (None: a place whose row has all its
        steps launched, or left), or None where there is no such step:
        nothing is in flight, none of its rows goes on, a request that needs
        a step is not among them (a join: its token is on the host), one of
        them speculates, or the rows left fit a smaller program."""
        flying = self._flying
        if flying is None:
            return None
        rows = [req if req is not None and req.slot is not None
                and req.step_idx < req.max_new else None
                for req in flying.rows]
        n = len(rows) - rows.count(None)
        if not n or any(req is not None and req.spec for req in rows):
            return None
        if n != sum(req.step_idx < req.max_new for req in self._active):
            return None
        if self._runtime.batch_bucket_for(n) != flying.step.tokens.shape[0]:
            return None
        return rows

    def _land(self):
        """Collect the step in flight, if there is one, and fan its tokens
        out: what makes the turn that follows a synchronous one."""
        flying, rt = self._flying, self._runtime
        if flying is None:
            return
        t0 = time.perf_counter()
        with _tel.span("decode.step", model=rt.name,
                       batch=flying.step.tokens.shape[0]):
            nxt = rt.collect(flying.step)
        self._flying = None
        self._fanout(flying, nxt, t0, time.perf_counter())

    def _fanout(self, flying, nxt, t0, t1):
        """One collected step's tokens to their requests.  A row that left
        while the step was in flight (it had ended by ``eos_id`` the step
        before, or was aborted) has no slot any more: its token is
        dropped."""
        rt = self._runtime
        rows = [(r, req) for r, req in enumerate(flying.rows)
                if req is not None and req.slot is not None]
        n = len(rows)
        with _tel.span("decode.step.fanout", rows=n,
                       batch_bucket=flying.step.tokens.shape[0]):
            if _tel.enabled:
                _tel.count("decode.steps", model=rt.name)
                _tel.count("decode.tokens", n, model=rt.name)
                _tel.observe("decode.step_ms", (t1 - t0) * 1e3)
                for _r, req in rows:
                    if req.ctx is not None:
                        # every step the request rode, on its own lane —
                        # "which steps served me" is visible per request
                        _tel.record_span("decode.ride_step", t0, t1,
                                         tid=req.lane, trace=req.ctx,
                                         model=rt.name, batch=n)
            finished = False
            for r, req in rows:
                req.cur = int(nxt[r])
                req.tokens.append(req.cur)
                if req.sink is not None:
                    req.sink._put(req.cur)
                if self._is_finished(req):
                    self._finish(req)
                    finished = True
            if finished:
                self._active = [req for req in self._active
                                if req.slot is not None]
        self._consecutive_failures = 0

    def _step_args(self, rows, b, tokens=None):
        """The plain step's host arrays for ``rows`` (None: a place nobody
        rides, padded like the places behind the batch), padded to batch
        bucket ``b``, behind the copy-on-write fence.  ``tokens``: the
        device's tokens of the step in flight, handed on unread; without
        them each row's last token, from the host."""
        cache = self._cache
        live = [(r, req) for r, req in enumerate(rows) if req is not None]
        if cache.prefix_sharing:
            # copy-on-write fence: the page each row is about to write
            # must be exclusively owned.  Admission already privatized
            # every write-path page (shared pages only ever cover the
            # prompt), so this is two refcount reads per row — but it is
            # the guard that makes "a shared page is never scribbled on"
            # an invariant instead of an accident.
            for _r, req in live:
                cache.ensure_writable(req.slot,
                                      req.position // cache.page_tokens)
        if tokens is None:
            tokens = np.zeros((b,), "int32")
            for r, req in live:
                tokens[r] = req.cur
        positions = np.zeros((b,), "int32")
        tables = np.zeros((b, cache.table_width), "int32")
        keys = np.zeros((b, 2), "uint32")
        steps = np.zeros((b,), "int32")
        temps = np.zeros((b,), "float32")
        for r, req in live:
            positions[r] = req.position
            tables[r] = req.slot.page_table
            keys[r] = req.key
            steps[r] = req.step_idx
            temps[r] = req.temp
        return tokens, positions, tables, keys, steps, temps

    def _collect_drafts(self):
        """Per-row draft proposals for this boundary, or ``None`` when
        nobody speculates (no drafter, every row opted out / budget-
        capped to zero, the drafter errored, or every draft came back
        empty) — the caller then runs the plain step program."""
        if self._drafter is None:
            return None
        ks = []
        for req in self._active:
            k = 0
            if req.spec:
                # budget cap: the verify commits at most k+1 tokens, so
                # k never exceeds the remaining budget minus one — the
                # last written position stays inside the page
                # reservation (prompt + max_new - 2)
                k = min(req.spec_state.k,
                        req.max_new - len(req.tokens) - 1)
            ks.append(max(k, 0))
        if not any(ks):
            return None
        try:
            proposed = self._drafter.propose_batch(self._active, ks)
        except Exception as e:
            _flight.record("decode.spec_draft_failure",
                           detail=f"{self._runtime.name}: {e!r}")
            if _tel.enabled:
                _tel.count("decode.spec_draft_failures",
                           model=self._runtime.name)
            return None
        vocab = self._runtime.block.vocab_size
        drafts, any_draft = [], False
        for d, k in zip(proposed, ks):
            d = np.asarray(d, "int32").reshape(-1)[:k]
            if d.size and (d.min() < 0 or d.max() >= vocab):
                d = _NO_DRAFT      # drafter bug: ids outside the vocab
            drafts.append(d)
            any_draft = any_draft or d.size > 0
        return drafts if any_draft else None

    def _spec_step(self, drafts):
        """One fused draft-verify step: write candidate K/V, score all
        drafted positions against the target's own deterministic sample
        stream, commit the accepted prefix plus the target's token at
        the first mismatch (or the bonus token when everything matched).
        Rolled-back K/V needs no cleanup — positions past the new
        ``req.position`` stay causally masked until a later boundary
        overwrites them."""
        rt = self._runtime
        n = len(self._active)
        kb = rt.spec_bucket_for(max(d.size for d in drafts))
        b = rt.batch_bucket_for(n)
        # the turn's second prepare span: _step's covered the drafting
        with _tel.span("decode.step.prepare", rows=n, batch_bucket=b):
            args = self._verify_args(drafts, b, kb)
        _flight.record("decode.spec_verify", detail=rt.name, value=n)
        t0 = time.perf_counter()
        target, n_acc = rt.verify(*args)
        t1 = time.perf_counter()
        with _tel.span("decode.step.fanout", rows=n, batch_bucket=b):
            self._commit_verified(drafts, target, n_acc, kb, t0, t1)
        self._consecutive_failures = 0

    def _verify_args(self, drafts, b, kb):
        """The verify program's host arrays: ``[cur, d_1 .. d_K]`` per
        active row, padded to batch bucket ``b`` and spec bucket ``kb``,
        every page the candidate span touches made private first."""
        cache = self._cache
        tokens = np.zeros((b, kb + 1), "int32")
        positions = np.zeros((b,), "int32")
        n_draft = np.zeros((b,), "int32")
        tables = np.zeros((b, cache.table_width), "int32")
        keys = np.zeros((b, 2), "uint32")
        steps = np.zeros((b,), "int32")
        temps = np.zeros((b,), "float32")
        for r, (req, d) in enumerate(zip(self._active, drafts)):
            tokens[r, 0] = req.cur
            if d.size:
                tokens[r, 1:1 + d.size] = d
            positions[r] = req.position
            n_draft[r] = d.size
            tables[r] = req.slot.page_table
            keys[r] = req.key
            steps[r] = req.step_idx
            temps[r] = req.temp
            if cache.prefix_sharing:
                # the verify writes positions [position, position + k]:
                # privatize EVERY page that span touches, not just the
                # current one (a draft can cross a page boundary)
                first = req.position // cache.page_tokens
                last = (req.position + int(d.size)) // cache.page_tokens
                for idx in range(first, last + 1):
                    cache.ensure_writable(req.slot, idx)
            if _san.slots:
                _san.check_kv_write_span(cache, req.slot, req.position,
                                         int(d.size) + 1)
        return tokens, positions, n_draft, tables, keys, steps, temps

    def _commit_verified(self, drafts, target, n_acc, kb, t0, t1):
        """Fan one verify's result out: per row the accepted drafts plus
        the target's own token, to the token lists, the sinks and the
        drafter's windows; finished rows leave the batch."""
        rt = self._runtime
        n = len(self._active)
        committed = 0
        still = []
        for r, (req, d) in enumerate(zip(self._active, drafts)):
            m = int(n_acc[r])
            finished = False
            for t in target[r, :m + 1]:
                req.cur = int(t)
                req.tokens.append(req.cur)
                if req.sink is not None:
                    req.sink._put(req.cur)
                req.position += 1
                req.step_idx += 1
                committed += 1
                if self._is_finished(req):
                    finished = True
                    break          # eos mid-commit: drop the tail
            if d.size:
                req.spec_state.observe(int(d.size), m)
                if _tel.enabled:
                    _tel.count("decode.spec_proposed", int(d.size),
                               model=rt.name)
                    _tel.count("decode.spec_accepted", m, model=rt.name)
                    if m == d.size:
                        _tel.count("decode.spec_bonus", model=rt.name)
                    _tel.observe("decode.spec_accept_rate",
                                 m / int(d.size))
            if finished:
                self._finish(req)
            else:
                if d.size:
                    try:
                        self._drafter.observe(req, int(d.size), m)
                    except Exception:
                        req.spec = False
                still.append(req)
        if _tel.enabled:
            _tel.count("decode.steps", model=rt.name)
            _tel.count("decode.spec_steps", model=rt.name)
            _tel.count("decode.tokens", committed, model=rt.name)
            _tel.observe("decode.step_ms", (t1 - t0) * 1e3)
            _tel.observe("decode.spec_tokens_per_step", committed / n)
            for req in self._active:
                if req.ctx is not None:
                    _tel.record_span("decode.ride_step", t0, t1,
                                     tid=req.lane, trace=req.ctx,
                                     model=rt.name, batch=n,
                                     spec_k=int(kb))
        self._active = still

    @staticmethod
    def _is_finished(req):
        if req.eos_id is not None and req.cur == req.eos_id:
            return True
        return len(req.tokens) >= req.max_new

    def _finish(self, req):
        reason = "eos" if (req.eos_id is not None
                           and req.cur == req.eos_id) else "length"
        self._evict(req, reason)
        latency = (time.perf_counter() - req.t_submit) * 1e3
        res = GenerationResult(req.tokens, reason, req.ttft_ms, latency,
                               req.prompt.size)
        req.future.set_result(res)
        if req.sink is not None:
            req.sink._finish(res)

    def _evict(self, req, reason):
        """Free a sequence's KV slot the moment it leaves the batch —
        continuous batching's whole point is that the next arrival can
        take these pages at the very next boundary."""
        if req.spec and self._drafter is not None:
            try:
                self._drafter.detach(req)
            except Exception:
                pass          # a leaky drafter must not block eviction
        if req.slot is not None:
            self._cache.free(req.slot)
            req.slot = None
        _flight.record("decode.evict", detail=reason)
        if _tel.enabled:
            _tel.count("decode.evictions", model=self._runtime.name,
                       reason=reason)
            if req.ctx is not None:
                # the lane's terminal mark, linked to the submit root —
                # the end of the request's journey in the merged trace
                _tel.instant("decode.evict", tid=req.lane, trace=req.ctx,
                             model=self._runtime.name, reason=reason)

    def _fail_active(self, exc, joining=()):
        """A prefill/step crash fails the requests that were in flight —
        their slots are freed, the worker survives, the breaker advances
        (consecutive failures open it).  A step's own failure surfaces
        where it is collected, a turn after its launch, with the next step
        already behind it: the rows of both are the active batch, failed
        here once, and what is in flight is dropped with them.
        ``joining`` covers requests admitted this boundary whose prefill
        never completed (they are not in the active list yet)."""
        self.steps_failed += 1
        _flight.record("decode.step_failure",
                       detail=f"{self._runtime.name}: {exc!r}")
        if _tel.enabled:
            _tel.count("decode.step_failures", model=self._runtime.name)
            _tel.instant("decode.step_failure", model=self._runtime.name,
                         error=repr(exc))
        in_active = set(map(id, self._active))
        for req in joining:
            if id(req) not in in_active and not req.future.done():
                self._evict(req, "failed")
                req.future.set_exception(exc)
                if req.sink is not None:
                    req.sink._fail(exc)
        for req in self._active:
            self._evict(req, "failed")
            if not req.future.done():
                req.future.set_exception(exc)
            if req.sink is not None:
                req.sink._fail(exc)
        self._active = []
        self._flying = None
        if self._breaker_threshold is None:
            return
        self._consecutive_failures += 1
        if self._consecutive_failures >= self._breaker_threshold:
            self._breaker_open_until = \
                time.perf_counter() + self._breaker_cooldown
            _flight.record("decode.breaker_open",
                           detail=self._runtime.name,
                           value=self._consecutive_failures)
            if _tel.enabled:
                _tel.count("decode.breaker_open", model=self._runtime.name)

    # ------------------------------------------------------------- shutdown
    def close(self, drain=True, timeout=60.0):
        """Stop the scheduler.  ``drain=True`` (default) finishes every
        queued and active request first; ``drain=False`` rejects the
        queue (``reason="shutdown"``) and fails active requests."""
        _http.unregister_ready(f"decode:{self._runtime.name}", self)
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._drain = bool(drain)
            worker = self._worker
            self._not_empty.notify_all()
            self._not_full.notify_all()
        if worker is not None and worker.is_alive():
            worker.join(timeout=timeout)
        if worker is not None and worker.is_alive():
            return      # hung worker: don't race it from this thread
        # no live worker (never started / crashed): settle inline
        if drain:
            while True:
                with self._lock:
                    if not self._queue and not self._running():
                        break
                self._boundary()
        else:
            with self._lock:
                self._abort_locked()

    def __del__(self):
        try:
            self.close(drain=False, timeout=1.0)
        except Exception:
            pass


class DecodeSession:
    """The one-stop ``generate()`` front-end: builds the
    :class:`~mxnet_tpu.serving.decode.runtime.DecodeRuntime` (2-D prefill
    grid + step programs, warmed) and the continuous-batching
    :class:`DecodeScheduler` around an initialized
    :class:`~mxnet_tpu.serving.decode.model.CausalLM`::

        net = mx.serving.decode.get_decode_model("decode_small")
        net.initialize()
        sess = mx.serving.decode.DecodeSession(net, page_size=16)
        out = sess.generate([5, 9, 2], max_new_tokens=32, temperature=0.8,
                            seed=7)
        out.token_ids, out.finish_reason, out.ttft_ms
        sess.close()

    ``submit()`` returns a Future for concurrent clients; requests join
    the running decode batch at step boundaries."""

    def __init__(self, block, batch_buckets=(1, 2, 4, 8), seq_buckets=None,
                 page_size=16, num_pages=None, max_slots=None,
                 kv_dtype=None, prefix_sharing=True, mesh=None,
                 queue_depth=256, warm=True, start=True, aot_cache=None,
                 drafter=None, spec_k=4, spec_buckets=None,
                 **scheduler_kwargs):
        if spec_buckets is None:
            # a drafter implies speculative decoding: one verify bucket
            # wide enough for the requested spec_k (adaptive per-request
            # k stays within it)
            spec_buckets = (int(spec_k),) if drafter is not None else ()
        self.runtime = DecodeRuntime(
            block, batch_buckets=batch_buckets, seq_buckets=seq_buckets,
            page_size=page_size, num_pages=num_pages, max_slots=max_slots,
            kv_dtype=kv_dtype, prefix_sharing=prefix_sharing,
            mesh=mesh, warm=warm, aot_cache=aot_cache,
            spec_buckets=spec_buckets)
        self.cache = self.runtime.cache
        self.scheduler = DecodeScheduler(
            self.runtime, queue_depth=queue_depth, start=start,
            drafter=drafter,
            spec_k=(min(int(spec_k), self.runtime.max_spec_k)
                    if drafter is not None else None),
            **scheduler_kwargs)

    def submit(self, prompt, **kwargs):
        return self.scheduler.submit(prompt, **kwargs)

    def generate(self, prompt, timeout=None, **kwargs):
        return self.scheduler.generate(prompt, timeout=timeout, **kwargs)

    def stream(self, prompt, **kwargs):
        """Incremental generation: a :class:`TokenStream` yielding ids as
        step boundaries commit them (the SSE data source)."""
        return self.scheduler.stream(prompt, **kwargs)

    def tokens(self, prompt, **kwargs):
        """Iterate token ids incrementally — alias for :meth:`stream`
        (the stream IS an iterator)."""
        return self.scheduler.stream(prompt, **kwargs)

    @property
    def device(self):
        return self.runtime.device

    @property
    def healthy(self):
        return self.scheduler.healthy

    @property
    def breaker_remaining_s(self):
        return self.scheduler.breaker_remaining_s

    def stats(self):
        s = self.cache.stats()
        s["pending"] = self.scheduler.pending()
        s["active"] = self.scheduler.active()
        s.update(device_info(self.device))
        if self.runtime.aot_cache is not None:
            s["aot_cache"] = self.runtime.aot_cache.stats()
        return s

    def close(self, drain=True, timeout=60.0):
        self.scheduler.close(drain=drain, timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=False)
        return False
