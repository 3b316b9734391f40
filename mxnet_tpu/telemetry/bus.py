"""Structured telemetry event bus — ring buffer of typed events.

The reference MXNet's observability is engine-integrated: every op execution
lands in the profiler's event stream (``src/profiler/profiler.h`` ring of
``ProfileEvent``s drained by the dump thread).  The TPU-native analog cannot
see per-op device events (XLA fuses them away), so this bus records the
*framework-level* events that decide TPU performance instead: eager-dispatch
jit-cache hits/misses, CachedOp recompiles, trainer step spans, kvstore
traffic, and IO pipeline stalls.

Design constraints (mirroring ``profiler.h``'s lock-free ring):

- **Off by default.** Every instrumentation site guards on the module-global
  ``enabled`` bool; a disabled check is one dict-free attribute read on the
  eager hot path.
- **Bounded memory.** Events land in a ``deque(maxlen=capacity)``: old events
  fall off instead of growing the heap on long runs.  Appends are GIL-atomic;
  counters take a small lock only when enabled.
- **Typed events.** ``("X", name, cat, ts, dur, tid, attrs, pid)`` spans,
  ``("I", ...)`` instants, ``("C", ...)`` counter samples — the exact shapes
  the chrome://tracing exporter needs, so export is a dumb translation.
  (``pid`` is the process *lane*: 1 by default, the simulated-host index
  once ``telemetry.trace`` resolves one — appended last so consumers that
  index earlier fields never move.)
- **Trace contexts.** A thread-local stack of ``(trace_id, span_id)`` pairs
  (managed by ``telemetry.trace``): while one is active, every span that
  closes on that thread stamps ``trace_id``/``span_id``/``parent_id`` into
  its attrs, which is what lets the exporter link a request's spans across
  threads and hosts.  ``record_span``/``instant`` accept explicit
  ``tid``/``pid``/``trace`` lane overrides for scopes measured on behalf
  of another lane (a decode request's ride through the batch, a worker
  process's decode span emitted by the consumer).
- **On the profiler's clock.** Every :class:`Span` also enters a
  ``jax.profiler.TraceAnnotation`` (:func:`annotation`, the one place that
  talks to it), so while a ``jax.profiler`` session runs, each framework
  span sits in the ``/host:CPU`` plane of the ``.xplane.pb`` under its own
  name, on the thread that ran it, on the device trace's time axis.  Spans
  recorded after the fact (``record_span``) stay on the bus clock only.
- **CPU time where a span asks for it.** ``span(name, cpu=True)`` lands the
  running thread's CPU time in ``cpu_ms``, ``cpu="process"`` the whole
  process's in ``proc_cpu_ms`` beside it: wall minus CPU is the time the
  thread stood off the CPU (the decode loop's turn and its step's launch
  ask; nothing else does, and a span that does not ask reads no CPU clock).

Enable via ``MXNET_TELEMETRY=1`` in the environment (checked at import) or
``mxnet_tpu.telemetry.enable()``.
"""
from __future__ import annotations

import os
import threading
import time
from bisect import bisect_left
from collections import deque

__all__ = ["enable", "disable", "is_enabled", "span", "count", "gauge",
           "instant", "counter_sample", "counter_value", "snapshot", "reset",
           "events", "record_span", "observe", "histogram_quantile",
           "histograms", "new_id", "trace_current", "open_spans",
           "annotation",
           "DEFAULT_CAPACITY", "HIST_BOUNDS"]

DEFAULT_CAPACITY = 65536

# Module-global fast-path flag: hot paths do ``if bus.enabled:`` — one
# attribute read when off.  Mutate only through enable()/disable().
enabled = False

# Process lane stamped on every event.  1 for a plain process; the
# simulated-host index once telemetry.trace.configure() resolves one, so a
# merged pod trace renders each host as its own Perfetto process group.
pid = 1

# Per-event stream hook (or None).  telemetry.trace points this at a
# per-host JSONL writer so events cross process boundaries the same way
# the divergence sanitizer's fingerprint streams do.  Only consulted while
# the bus is enabled; a hook failure must never break an instrumented site.
stream = None

_lock = threading.RLock()
_events = deque(maxlen=DEFAULT_CAPACITY)
_counters = {}      # name -> float (total over all label sets)
_labeled = {}       # name -> {(("k", "v"), ...) -> float}
_gauges = {}        # name -> value
_span_agg = {}      # name -> [calls, total_seconds]
_hists = {}         # name -> [bucket_counts, sum, count, min, max]
_open_spans = {}    # id(Span) -> (name, t0_seconds, tid) — live spans
_epoch = time.perf_counter()   # trace timestamps are relative to this

# Thread-local trace-context stack: list of (trace_id, span_id) pairs.
# telemetry.trace pushes/pops request/step roots; Span nests under the top.
_tls = threading.local()

_id_lock = threading.Lock()
_id_count = 0
# id seed: os pid in the high bits so two processes writing one merged
# trace can't mint colliding span ids; telemetry.trace folds the host
# index in when a simulated-host identity resolves.
_id_seed = (os.getpid() & 0xfffff) << 28


def new_id():
    """A fresh process-unique span/trace id (int, chrome-trace friendly)."""
    global _id_count
    with _id_lock:
        _id_count += 1
        return _id_seed | _id_count


def trace_current():
    """Top of this thread's trace-context stack: ``(trace_id, span_id)``
    or None.  The user-facing API lives in :mod:`.trace`."""
    s = getattr(_tls, "trace", None)
    return s[-1] if s else None


def _trace_stack():
    s = getattr(_tls, "trace", None)
    if s is None:
        s = _tls.trace = []
    return s


def _now_us():
    return (time.perf_counter() - _epoch) * 1e6


def enable(capacity=None):
    """Turn the bus on (idempotent).  ``capacity`` resizes the ring."""
    global enabled, _events
    with _lock:
        if capacity is not None and capacity != _events.maxlen:
            _events = deque(_events, maxlen=int(capacity))
        enabled = True
    from . import jax_hooks
    jax_hooks.install()


def disable():
    """Turn the bus off.  Recorded events/counters are kept until reset()."""
    global enabled
    enabled = False


def is_enabled():
    return enabled


def reset():
    """Drop all recorded events, counters, gauges, histograms and span
    aggregates."""
    with _lock:
        _events.clear()
        _counters.clear()
        _labeled.clear()
        _gauges.clear()
        _span_agg.clear()
        _hists.clear()


def events():
    """Snapshot of the raw event tuples currently in the ring."""
    with _lock:
        return list(_events)


# ------------------------------------------------------------------ counters
def count(name, value=1, **labels):
    """Add ``value`` to counter ``name``; returns the new total.

    Labels create a secondary per-label-set breakdown (e.g.
    ``count("dispatch.op_calls", op="broadcast_add")``) on top of the
    flat total that ``snapshot()``/``dump_metrics()`` report.
    """
    if not enabled:
        return 0
    with _lock:
        total = _counters.get(name, 0) + value
        _counters[name] = total
        if labels:
            key = tuple(sorted(labels.items()))
            per = _labeled.setdefault(name, {})
            per[key] = per.get(key, 0) + value
    return total


def counter_value(name):
    """Current total of a counter (0 if never written)."""
    return _counters.get(name, 0)


def _label_str(items):
    """Prometheus-style label block from sorted (key, value) pairs —
    the single place the ``{k="v"}`` syntax is produced."""
    return "{" + ",".join(f'{k}="{v}"' for k, v in items) + "}"


def gauge(name, value, **labels):
    """Set gauge ``name`` to ``value`` (last-write-wins)."""
    if not enabled:
        return
    with _lock:
        if labels:
            _gauges[name + _label_str(sorted(labels.items()))] = value
        else:
            _gauges[name] = value


# --------------------------------------------------------------- histograms
# Fixed log2 bucket ladder (Prometheus ``le`` upper bounds): 2^-4 .. 2^20
# covers 0.06 ms queue waits through ~17-minute outliers with one shared
# layout, so merging/exporting never has to reconcile per-name boundaries.
HIST_BOUNDS = tuple(float(2.0 ** e) for e in range(-4, 21))


def observe(name, value):
    """Record ``value`` into histogram ``name`` (fixed log2 buckets).

    The recording sites are latency-shaped (decode TTFT, per-step decode
    latency, serving queue wait — all in ms); percentiles come back via
    :func:`histogram_quantile` / :func:`snapshot` and the Prometheus
    ``_bucket`` series via ``dump_metrics()``."""
    if not enabled:
        return
    value = float(value)
    idx = bisect_left(HIST_BOUNDS, value)
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = [[0] * (len(HIST_BOUNDS) + 1), 0.0, 0,
                                value, value]
        h[0][idx] += 1
        h[1] += value
        h[2] += 1
        if value < h[3]:
            h[3] = value
        if value > h[4]:
            h[4] = value


def histogram_quantile(name, q):
    """Estimate quantile ``q`` (0..1) of histogram ``name`` by linear
    interpolation inside the containing bucket (the standard Prometheus
    ``histogram_quantile`` estimate).  None if nothing was observed."""
    with _lock:
        h = _hists.get(name)
        if h is None or h[2] == 0:
            return None
        buckets, _total, count, minv, maxv = \
            list(h[0]), h[1], h[2], h[3], h[4]
    target = max(min(float(q), 1.0), 0.0) * count
    cum = 0
    for i, c in enumerate(buckets):
        cum += c
        if cum >= target and c:
            lo = HIST_BOUNDS[i - 1] if i > 0 else 0.0
            hi = HIST_BOUNDS[i] if i < len(HIST_BOUNDS) else maxv
            lo, hi = max(lo, minv) if i == 0 else lo, min(hi, maxv)
            frac = (target - (cum - c)) / c
            return lo + (hi - lo) * frac
    return maxv


def histograms():
    """``{name: {"count", "sum", "min", "max", "buckets": [(le, cum), ...]}}``
    with *cumulative* bucket counts (``le`` is the Prometheus upper bound;
    the last entry is ``("+Inf", count)``)."""
    out = {}
    with _lock:
        items = [(name, (list(h[0]), h[1], h[2], h[3], h[4]))
                 for name, h in _hists.items()]
    for name, (buckets, total, count, minv, maxv) in items:
        cum, rows = 0, []
        for i, c in enumerate(buckets):
            cum += c
            le = HIST_BOUNDS[i] if i < len(HIST_BOUNDS) else "+Inf"
            rows.append((le, cum))
        out[name] = {"count": count, "sum": total, "min": minv,
                     "max": maxv, "buckets": rows}
    return out


# ------------------------------------------------------------------- events
def _append(ev):
    _events.append(ev)
    if stream is not None:
        try:
            stream(ev)
        except Exception:
            pass    # a full disk must not take the instrumented site down


def counter_sample(name, value=None):
    """Emit a 'C' trace event sampling a counter's current value — gives
    hot counters (eager dispatch) a presence in the chrome trace without
    one event per increment."""
    if not enabled:
        return
    if value is None:
        value = _counters.get(name, 0)
    _append(("C", name, name.split(".", 1)[0], _now_us(), 0,
             threading.get_ident(), {"value": value}, pid))


def instant(name, tid=None, pid=None, trace=None, **attrs):
    """Record an instant event (chrome 'i' phase).

    ``tid``/``pid``/``trace`` are reserved lane parameters, not attrs:
    ``tid``/``pid`` place the instant on an explicit thread/process lane,
    ``trace`` (a 3-tuple ``(trace_id, span_id, parent_id)`` or a
    ``TraceContext``) stamps trace linkage into the attrs."""
    if not enabled:
        return
    if trace is not None:
        attrs = _stamp_trace(attrs, trace)
    _append(("I", name, name.split(".", 1)[0], _now_us(), 0,
             tid if tid is not None else threading.get_ident(),
             attrs or None,
             pid if pid is not None else globals()["pid"]))


# -------------------------------------------------------------------- spans
class _NoopSpan:
    """Shared do-nothing span handed out when the bus is off."""

    __slots__ = ()
    attrs = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()

_TraceAnnotation = None     # jax.profiler's class, imported at first use


def annotation(name, attrs=None):
    """Enter and return a ``jax.profiler.TraceAnnotation`` called ``name``
    carrying the scalar ``attrs`` — the ONE place the framework talks to
    it (:class:`Span` and ``profiler._Scope`` both come here).  The caller
    leaves it with ``__exit__(None, None, None)``.  With no profiler
    session running an annotation records nothing."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    scalars = {k: v for k, v in attrs.items()
               if isinstance(v, (bool, int, float, str))} if attrs else {}
    ann = _TraceAnnotation(name, **scalars)
    ann.__enter__()
    return ann


class Span:
    """Timed scope that lands as one complete ('X') trace event on exit
    and feeds the per-name aggregate that ``profiler.dumps()`` shows.

    While a trace context is active on this thread (a request/step root
    pushed by :mod:`.trace`), entering a span mints a child span id and
    pushes it, so nested spans form a parent→child chain the exporter can
    render as flow arrows; exit stamps ``trace_id``/``span_id``/
    ``parent_id`` into the attrs.  Open spans are registered for the
    flight recorder's "what was in flight" post-mortem section.

    The span is also a profiler annotation (:func:`annotation`) with the
    attrs it was opened with; ones :meth:`set` later reach the bus event
    only.

    ``cpu`` (see :func:`span`) makes the span read the CPU clocks just
    outside its two ends and land ``cpu_ms`` (and ``proc_cpu_ms``) in its
    attrs at exit."""

    __slots__ = ("name", "attrs", "_t0", "_trace", "_ann", "_cpu")

    def __init__(self, name, attrs, cpu=False):
        self.name = name
        self.attrs = attrs
        self._t0 = None
        self._trace = None
        self._ann = None
        self._cpu = cpu

    def set(self, **attrs):
        """Attach attributes mid-span (shows in the trace event args)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        stack = getattr(_tls, "trace", None)
        if stack:
            parent_trace, parent_span = stack[-1]
            sid = new_id()
            stack.append((parent_trace, sid))
            self._trace = (parent_trace, sid, parent_span)
        if self._cpu:
            # a CPU clock is a system call: read before the span opens and
            # after it has closed, so that neither its duration nor its
            # annotation holds a read
            self._cpu = (time.thread_time(),
                         time.process_time() if self._cpu == "process"
                         else None)
        self._t0 = time.perf_counter()
        _open_spans[id(self)] = (self.name, self._t0,
                                 threading.get_ident())
        self._ann = annotation(self.name, self.attrs)
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        t1 = None
        if self._cpu:
            t1 = time.perf_counter()
            t_cpu, p_cpu = self._cpu
            self.attrs["cpu_ms"] = (time.thread_time() - t_cpu) * 1e3
            if p_cpu is not None:
                self.attrs["proc_cpu_ms"] = \
                    (time.process_time() - p_cpu) * 1e3
        # the stack pop must happen even if the bus was disabled mid-span,
        # or the thread's context stack would corrupt for every later span
        if self._trace is not None:
            stack = getattr(_tls, "trace", None)
            if stack:
                stack.pop()
        _open_spans.pop(id(self), None)
        if self._t0 is None or not enabled:
            # a span still open when disable() lands (e.g. a prefetch
            # thread mid-batch) must not pollute the post-disable window
            return False
        # attrs as a dict, NOT **kwargs: an attribute named t1/name/t0
        # must stay an attribute, not collide with record_span's params
        _emit_span(self.name, self._t0, t1, self.attrs or None,
                   trace=self._trace)
        return False


def span(name, cpu=False, **attrs):
    """Start a timed scope: ``with telemetry.span("trainer.step"): ...``.
    Returns a shared no-op when the bus is disabled.

    ``cpu`` is a reserved parameter, not an attr.  ``cpu=True`` lands
    ``cpu_ms`` in the span's attrs at exit: the CPU time of the thread
    that ran it (``time.thread_time()``, read just before the span opens
    and just after it has closed: its own ``dur`` holds neither read, and
    ``cpu_ms`` may pass ``dur`` by a read's tail), so ``dur - cpu_ms`` is
    the time that thread was off the CPU inside the span: blocked in the
    runtime, or runnable and waiting for the interpreter's lock.
    ``cpu="process"`` also lands ``proc_cpu_ms`` (``time.process_time()``:
    every thread of the process), so ``proc_cpu_ms - cpu_ms`` is what the
    OTHER threads burned meanwhile.  A read is a system call (6 us on the
    chip's host, whose CPU clocks tick in 10 ms: one span reads 0 or a
    tick, sums over many are unbiased), so ask where a metric reads the
    answer.  Off by default: a span that does not ask reads no CPU clock."""
    if not enabled:
        return _NOOP
    return Span(name, attrs, cpu)


def open_spans():
    """Live (entered, not yet exited) spans as ``(name, t0_seconds, tid)``
    rows — the flight recorder's "active spans" post-mortem section."""
    return list(_open_spans.values())


def record_span(name, t0, t1=None, tid=None, pid=None, trace=None, **attrs):
    """Record an already-timed scope as a complete ('X') span event.

    For scopes measured across threads — e.g. a serving request's queue wait
    between ``submit()`` (client thread) and dequeue (batcher worker) — a
    ``with span(...)`` cannot bracket the code; the caller stamps
    ``time.perf_counter()`` at both ends instead.  Feeds the same per-name
    aggregates as :class:`Span`.

    ``tid``/``pid``/``trace`` are reserved lane parameters (not attrs):
    ``tid``/``pid`` place the span on an explicit thread/process lane —
    a per-request lane, an io worker's process — and ``trace`` (a 3-tuple
    ``(trace_id, span_id, parent_id)`` or a ``TraceContext``, which mints
    a child id) stamps trace linkage."""
    if not enabled:
        return
    _emit_span(name, t0, t1, attrs or None, tid=tid, pid=pid, trace=trace)


def _stamp_trace(attrs, trace):
    """Normalize a ``trace`` argument into trace_id/span_id/parent_id attrs.
    Accepts the explicit 3-tuple or any object with ``trace_id``/``span_id``
    (a ``trace.TraceContext``) — the latter mints a fresh child span id."""
    if not isinstance(trace, tuple):
        trace = (trace.trace_id, new_id(), trace.span_id)
    attrs = dict(attrs) if attrs else {}
    attrs["trace_id"], attrs["span_id"], attrs["parent_id"] = trace
    return attrs


def _emit_span(name, t0, t1, attrs, tid=None, pid=None, trace=None):
    """Shared emit for Span.__exit__ and record_span — ONE place owns the
    ('X', ...) event layout and the per-name aggregate shape."""
    if t1 is None:
        t1 = time.perf_counter()
    dt = max(t1 - t0, 0.0)
    if trace is not None:
        attrs = _stamp_trace(attrs, trace)
    _append(("X", name, name.split(".", 1)[0], (t0 - _epoch) * 1e6,
             dt * 1e6, tid if tid is not None else threading.get_ident(),
             attrs, pid if pid is not None else globals()["pid"]))
    with _lock:
        row = _span_agg.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += dt


def span_aggregates():
    """``{name: (calls, total_seconds)}`` over all closed spans."""
    with _lock:
        return {k: (v[0], v[1]) for k, v in _span_agg.items()}


# ----------------------------------------------------------------- snapshot
def snapshot():
    """One dict with everything the bus knows — usable from tests, the
    benchmark's drivers and monitor callbacks without touching exporters."""
    hist = {name: {"count": row["count"],
                   "sum": round(row["sum"], 3),
                   "min": round(row["min"], 3),
                   "max": round(row["max"], 3),
                   "p50": round(histogram_quantile(name, 0.50) or 0.0, 3),
                   "p90": round(histogram_quantile(name, 0.90) or 0.0, 3),
                   "p99": round(histogram_quantile(name, 0.99) or 0.0, 3)}
            for name, row in histograms().items()}
    with _lock:
        return {
            "enabled": enabled,
            "counters": dict(_counters),
            "counters_by_label": {
                name: {_label_str(key): val for key, val in per.items()}
                for name, per in _labeled.items()},
            "gauges": dict(_gauges),
            "spans": {name: {"calls": c, "total_ms": round(t * 1e3, 3)}
                      for name, (c, t) in _span_agg.items()},
            "histograms": hist,
            "n_events": len(_events),
        }


if os.environ.get("MXNET_TELEMETRY", "0") not in ("0", "", "false"):
    enable()
