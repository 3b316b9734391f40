"""Profiler (reference ``python/mxnet/profiler.py`` over ``src/profiler/``).

The reference's engine-integrated profiler records per-op events into a
chrome://tracing JSON plus an aggregate per-op table (``aggregate_stats.cc``).
TPU-native mapping: ``jax.profiler`` emits XPlane/perfetto traces of the real
XLA executables (the honest per-op story once fusion exists), and this module
keeps the reference's control surface — ``set_config/start/stop/dump`` and
scoped ``Task/Frame/Marker`` annotations that land in the trace via
``jax.profiler.TraceAnnotation`` — plus a wall-clock aggregate table for the
``dumps()`` UX.
"""
from __future__ import annotations

import os
import time
import warnings
import weakref

_config = {"profile_all": False, "profile_symbolic": True,
           "profile_imperative": True, "profile_memory": False,
           "profile_api": False, "filename": "profile.json",
           "aggregate_stats": False}
_state = {"running": False, "dir": None, "preexisting": set()}
_aggregate = {}
_parse_cache = {}
# live Counter objects (weak so a dropped Counter leaves the table) —
# dumps() reads their CURRENT values; previously Counter was write-only
_counters = weakref.WeakSet()


def set_config(**kwargs):
    """Reference ``profiler.py:set_config``; ``filename`` decides the trace
    output directory."""
    _config.update(kwargs)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Deprecated alias (reference keeps it)."""
    warnings.warn("profiler.profiler_set_config is deprecated; use set_config")
    _config["filename"] = filename


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):
    """Start tracing (reference ``profiler.py:start``)."""
    import jax
    if _state["running"]:
        return
    logdir = os.path.splitext(_config["filename"])[0] + "_trace"
    os.makedirs(logdir, exist_ok=True)
    # only THIS session's trace run feeds the aggregate table — the trace
    # dir persists across sessions/processes and accumulates runs
    _state["preexisting"] = set(_find_xplanes(logdir))
    _parse_cache.clear()
    try:
        jax.profiler.start_trace(logdir)
        _state["dir"] = logdir
    except Exception as e:  # tracing backend unavailable (e.g. in tests)
        warnings.warn(f"jax.profiler trace unavailable: {e}")
        _state["dir"] = None
    _state["running"] = True


def stop(profile_process="worker"):
    import jax
    if not _state["running"]:
        return
    if _state["dir"] is not None:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
    _state["running"] = False


def pause(profile_process="worker"):
    stop()


def resume(profile_process="worker"):
    start()


def dump(finished=True, profile_process="worker"):
    """Finalize the trace (the XPlane files under ``<filename>_trace`` are
    the chrome://tracing analog — open with TensorBoard/perfetto)."""
    stop()


def _find_xplanes(logdir):
    out = []
    for root, _dirs, files in os.walk(logdir):
        out.extend(os.path.join(root, f) for f in files
                   if f.endswith(".xplane.pb"))
    return sorted(out)


def _xplane_aggregate(logdir):
    """Per-op aggregate from the captured XPlane trace (the reference's
    ``src/profiler/aggregate_stats.cc`` over real engine events; here the
    events are the XLA executables'/ops' actual device timings).

    Returns ``{op_name: [count, total_s, min_s, max_s]}`` from device
    planes (host planes are the fallback when the backend exposes no
    device plane, e.g. pure-host runs)."""
    files = [f for f in _find_xplanes(logdir)
             if f not in _state.get("preexisting", ())]
    if not files:
        return None
    key = frozenset(files)
    if key in _parse_cache:             # a finished trace is immutable
        return _parse_cache[key]
    from jax.profiler import ProfileData
    agg, rt_agg = {}, {}
    for path in files:
        for plane in ProfileData.from_file(path).planes:
            plane_is_device = "/device:" in plane.name.lower()
            for line in plane.lines:
                lname = line.name.lower()
                if plane_is_device:
                    if "step" in lname:
                        continue        # step-number markers, not ops
                    target = agg        # TPU: lines are XLA ops/modules
                elif lname.startswith("tf_xlapjrt"):
                    target = rt_agg     # host runtime executing XLA thunks
                else:
                    continue            # python frames, codegen, metadata
                for ev in line.events:
                    name = ev.name
                    # drop region markers and C++ runtime internals — keep
                    # the op/fusion executions the table is about
                    if not name or name.startswith("end: ") or "::" in name:
                        continue
                    dur = ev.duration_ns / 1e9
                    row = target.setdefault(name, [0, 0.0, float("inf"),
                                                   0.0])
                    row[0] += 1
                    row[1] += dur
                    row[2] = min(row[2], dur)
                    row[3] = max(row[3], dur)
    result = agg or rt_agg or None
    _parse_cache[key] = result
    return result


_SORT_COL = {"total": lambda r: r[1][1], "count": lambda r: r[1][0],
             "min": lambda r: r[1][2], "max": lambda r: r[1][3],
             "avg": lambda r: r[1][1] / max(r[1][0], 1),
             "name": lambda r: r[0]}


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate stats table (reference ``profiler.py:dumps`` →
    ``aggregate_stats.cc``): per-op device timings parsed from the captured
    XPlane trace, plus the Python-side annotation scopes."""
    key = _SORT_COL.get(sort_by, _SORT_COL["total"])
    lines = []
    trace_agg = _xplane_aggregate(_state["dir"]) if _state["dir"] else None
    if trace_agg:
        lines.append("Device ops (from XPlane trace)")
        lines.append("%-50s %8s %12s %12s %12s %12s" % (
            "Name", "Calls", "Total(ms)", "Min(ms)", "Max(ms)", "Avg(ms)"))
        rows = sorted(trace_agg.items(), key=key, reverse=not ascending)
        for name, (calls, total, mn, mx) in rows:
            lines.append("%-50s %8d %12.3f %12.3f %12.3f %12.3f" % (
                name[:50], calls, total * 1e3, mn * 1e3, mx * 1e3,
                total / calls * 1e3))
        lines.append("")
    lines.append("Annotation scopes (host wall clock)")
    lines.append("%-50s %8s %12s" % ("Name", "Calls", "Total(ms)"))
    for name, (calls, total) in sorted(_aggregate.items(),
                                       key=lambda kv: -kv[1][1]):
        lines.append("%-50s %8d %12.3f" % (name[:50], calls, total * 1e3))
    counter_rows = sorted((c.name, c.value) for c in _counters)
    if counter_rows:
        lines.append("")
        lines.append("Counters")
        lines.append("%-50s %12s" % ("Name", "Value"))
        for name, value in counter_rows:
            lines.append("%-50s %12s" % (name[:50], value))
    lines.extend(_telemetry_section())
    if reset:
        _aggregate.clear()
    return "\n".join(lines)


def _telemetry_section():
    """Framework events recorded by ``mxnet_tpu.telemetry`` — shown in the
    same aggregate-table UX as the reference's per-op rows, so one
    ``dumps()`` answers both "what ran on device" (XPlane section) and
    "what did the framework do" (spans + counters)."""
    from . import telemetry
    snap = telemetry.snapshot()
    if not (snap["spans"] or snap["counters"] or snap.get("histograms")):
        return []
    lines = ["", "Framework events (telemetry)"]
    if snap["spans"]:
        lines.append("%-50s %8s %12s" % ("Span", "Calls", "Total(ms)"))
        for name, row in sorted(snap["spans"].items(),
                                key=lambda kv: -kv[1]["total_ms"]):
            lines.append("%-50s %8d %12.3f" % (name[:50], row["calls"],
                                               row["total_ms"]))
    if snap["counters"]:
        lines.append("%-50s %12s" % ("Counter", "Value"))
        for name, value in sorted(snap["counters"].items()):
            val = round(value, 3) if isinstance(value, float) else value
            lines.append("%-50s %12s" % (name[:50], val))
    if snap.get("histograms"):
        # latency distributions straight from the histogram buckets — no
        # span mining needed to answer "what was p99 TTFT?"
        lines.append("%-38s %8s %9s %9s %9s %9s" %
                     ("Histogram", "Count", "p50", "p90", "p99", "Max"))
        for name, row in sorted(snap["histograms"].items()):
            lines.append("%-38s %8d %9.3f %9.3f %9.3f %9.3f" %
                         (name[:38], row["count"], row["p50"], row["p90"],
                          row["p99"], row["max"]))
    return lines


class _Scope:
    """Timed, trace-annotated scope."""

    def __init__(self, name):
        self._name = name
        self._t0 = None
        self._ann = None

    def start(self):
        from .telemetry import bus
        self._t0 = time.perf_counter()
        self._ann = bus.annotation(self._name)

    def stop(self):
        if self._t0 is None:
            return
        dt = time.perf_counter() - self._t0
        calls, total = _aggregate.get(self._name, (0, 0.0))
        _aggregate[self._name] = (calls + 1, total + dt)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


class Domain:
    """Profiling domain (reference ``profiler.py:Domain``)."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class Task(_Scope):
    def __init__(self, domain, name):
        super().__init__(f"{domain.name}::{name}")
        self.name = name


class Frame(_Scope):
    def __init__(self, domain, name):
        super().__init__(f"{domain.name}::{name}")
        self.name = name


class Event(_Scope):
    def __init__(self, name):
        super().__init__(name)
        self.name = name


class Counter:
    def __init__(self, domain, name, value=None):
        self.name = f"{domain.name}::{name}"
        self.value = value or 0
        _counters.add(self)   # read back by dumps() — values are live

    def set_value(self, value):
        self.value = value

    def increment(self, delta=1):
        self.value += delta

    def decrement(self, delta=1):
        self.value -= delta

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.name = f"{domain.name}::{name}"

    def mark(self, scope="process"):
        calls, total = _aggregate.get(self.name, (0, 0.0))
        _aggregate[self.name] = (calls + 1, total)
