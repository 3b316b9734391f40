"""Telemetry exporters: chrome://tracing JSON and Prometheus text.

The chrome exporter mirrors the reference profiler's output contract
(``src/profiler/profiler.cc EmitEvents`` writes a chrome trace the user
opens in chrome://tracing or perfetto); the Prometheus dump gives scrapers
and tests a flat text form of the counters/gauges/histograms.  The merged
multi-host/flow-linked export lives in :func:`.trace.chrome_trace` (it
needs the per-host stream state); this module owns the dumb per-event
translation both exporters share.
"""
from __future__ import annotations

import json
import re

from . import bus

__all__ = ["trace_events", "event_dict", "dump_trace", "dump_metrics"]

_PROCESS_NAME = "mxnet_tpu"


def event_dict(ev):
    """ONE bus event tuple → its chrome trace-event dict (ts/dur in us).
    Shared by the ring exporter below and the per-host stream writer in
    :mod:`.trace`, so the two serializations can never drift."""
    kind, name, cat, ts, dur, tid, attrs, pid = ev
    out = {"name": name, "cat": cat, "ts": round(ts, 3), "pid": pid,
           "tid": tid}
    if kind == "X":
        out["ph"] = "X"
        out["dur"] = round(dur, 3)
    elif kind == "I":
        out["ph"] = "i"
        out["s"] = "t"       # thread-scoped instant
    elif kind == "C":
        out["ph"] = "C"
    if attrs:
        out["args"] = {k: v for k, v in attrs.items()}
    return out


def trace_events():
    """The ring's events as chrome trace-event dicts (ts/dur in us)."""
    return [event_dict(ev) for ev in bus.events()]


def dump_trace(path=None):
    """Write (or return) a chrome://tracing-loadable JSON object with every
    span/instant/counter-sample currently in the ring, plus one metadata
    event naming the process.  ``path=None`` returns the dict.

    Single-process export; :func:`.trace.chrome_trace` is the merged
    multi-host form with flow links between parent and child spans."""
    events = [{"name": "process_name", "ph": "M", "pid": bus.pid, "tid": 0,
               "args": {"name": _PROCESS_NAME}}]
    events.extend(trace_events())
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc


_METRIC_OK = re.compile(r"[^a-zA-Z0-9_]")


def _prom_name(name):
    return "mxnet_" + _METRIC_OK.sub("_", name)


def _fmt_le(le):
    if le == "+Inf":
        return "+Inf"
    return repr(float(le))


def dump_metrics():
    """Prometheus-style text exposition of counters, gauges and histograms.

    Counter totals come first, then per-label breakdowns, then gauges;
    span aggregates export as ``_calls`` / ``_total_ms`` pairs; histograms
    as cumulative ``_bucket{le=...}`` series plus ``_sum``/``_count``."""
    snap = bus.snapshot()
    lines = []
    for name in sorted(snap["counters"]):
        metric = _prom_name(name)
        lines.append(f"# TYPE {metric} counter")
        lines.append(f"{metric} {snap['counters'][name]}")
        for labels, val in sorted(
                snap["counters_by_label"].get(name, {}).items()):
            lines.append(f"{metric}{labels} {val}")
    typed = set()
    for name in sorted(snap["gauges"]):
        base, _, labels = name.partition("{")
        metric = _prom_name(base)
        if metric not in typed:     # one TYPE line per family, not per
            typed.add(metric)       # label set
            lines.append(f"# TYPE {metric} gauge")
        suffix = "{" + labels if labels else ""
        lines.append(f"{metric}{suffix} {snap['gauges'][name]}")
    for name in sorted(snap["spans"]):
        row = snap["spans"][name]
        metric = _prom_name(name)
        lines.append(f"# TYPE {metric}_calls counter")
        lines.append(f"{metric}_calls {row['calls']}")
        lines.append(f"# TYPE {metric}_total_ms counter")
        lines.append(f"{metric}_total_ms {row['total_ms']}")
    for name, row in sorted(bus.histograms().items()):
        metric = _prom_name(name)
        lines.append(f"# TYPE {metric} histogram")
        for le, cum in row["buckets"]:
            lines.append(f'{metric}_bucket{{le="{_fmt_le(le)}"}} {cum}')
        lines.append(f"{metric}_sum {round(row['sum'], 6)}")
        lines.append(f"{metric}_count {row['count']}")
    return "\n".join(lines) + ("\n" if lines else "")
