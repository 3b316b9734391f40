"""Per-layer metric readers, by kind.  A metric is its own file
``perf/metrics/<name>.json``: ``{"reader": {"kind": ..., ...}}``.  A reader
takes what the run observed (``obs``) and returns a number, or ``None``
where it found nothing to read — the harness then leaves the metric out.
A metric that needs new arithmetic names ``{"kind": "python"}`` and brings
``perf/metrics/<name>.py`` with ``read(obs, spec)``.

``obs`` keys: ``counters`` and ``histograms`` (the program's telemetry),
``spans`` ``[(name, start_s, dur_s, attrs)]``, ``flight`` ``[(t, name,
detail, value)]``, ``samples`` ``{name: [numbers]}`` and ``values``
``{name: number}`` (the driver's own observations), ``trace`` (a
``trace_reduce.Reduced`` or None), ``memory_peak_bytes``, ``cell``,
``peaks``, ``chips``.
"""
import importlib
import importlib.util

from . import stats


def counter_ratio(obs, spec):
    c = obs.get("counters") or {}
    num, den = c.get(spec["numerator"]), c.get(spec["denominator"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


def histogram_quantile(obs, spec):
    fn = obs.get("histogram_quantile")
    return fn(spec["histogram"], spec["q"]) if fn else None


def _spans(obs, spec):
    need = spec.get("has_attrs", [])
    return [(s, d) for name, s, d, attrs in obs.get("spans") or []
            if name == spec["span"] and all(k in (attrs or {}) for k in need)]


def span_percentile(obs, spec):
    durs = [d * 1e3 for _s, d in _spans(obs, spec)]
    return stats.percentile(durs, spec["q"]) if durs else None


def sample_percentile(obs, spec):
    vals = (obs.get("samples") or {}).get(spec["sample"])
    return stats.percentile(vals, spec["q"]) if vals else None


def sample_mean(obs, spec):
    vals = (obs.get("samples") or {}).get(spec["sample"])
    return spec.get("scale", 1.0) * stats.mean(vals) if vals else None


def value(obs, spec):
    v = (obs.get("values") or {}).get(spec["value"])
    return None if v is None else spec.get("scale", 1.0) * v


def flight_share_at_most(obs, spec):
    """Share of flight-recorder events ``event`` whose value is positive
    and at most ``at_most``."""
    vals = [val for _t, name, _detail, val in obs.get("flight") or []
            if name == spec["event"] and val]
    if not vals:
        return None
    return 100.0 * sum(v <= spec["at_most"] for v in vals) / len(vals)


def memory_peak_gb(obs, spec):
    b = obs.get("memory_peak_bytes")
    return b / 1e9 if b else None


def trace_idle_share(obs, spec):
    tr = obs.get("trace")
    if tr is None or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def _step_device_seconds(tr, spec):
    n, total = tr.module_seconds(spec["step_module"])
    return total / n if n else None


def trace_op_share(obs, spec):
    """Device seconds of the operations whose HLO text matches ``patterns``
    (``{seq_len}`` and the like are filled from the traffic file) over the
    device seconds of the step programs, as a percentage."""
    tr = obs.get("trace")
    if tr is None:
        return None
    fill = {k: v for k, v in obs["cell"].traffic.items()
            if isinstance(v, (int, str))}
    pats = [p.format(**fill) for p in spec["patterns"]]
    _n, whole = tr.module_seconds(spec["step_module"])
    if not whole:
        return None
    return 100.0 * tr.matching_seconds(pats) / whole


KINDS = {f.__name__: f for f in (
    counter_ratio, histogram_quantile, span_percentile,
    sample_percentile, sample_mean, value,
    flight_share_at_most, memory_peak_gb, trace_idle_share, trace_op_share)}


def read_metric(name, obs):
    """The metric's number, or None.  Looks the reader up by the kind its
    file names; ``python`` imports ``perf/metrics/<name>.py``."""
    spec = obs["cell"].metric_file(name)["reader"]
    if spec["kind"] == "python":
        import os
        path = os.path.join(obs["cell"].bench_dir, "metrics", name + ".py")
        mspec = importlib.util.spec_from_file_location(
            "perf_metric_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(mspec)
        mspec.loader.exec_module(mod)
        return mod.read(obs, spec)
    return KINDS[spec["kind"]](obs, spec)
