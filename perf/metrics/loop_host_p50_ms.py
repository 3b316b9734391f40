"""The host's part of one turn of the scheduler's loop: per
``decode.boundary`` span its duration minus the runtime's program spans
(``decode.step``, ``decode.prefill``, ``decode.verify``: dispatch to fetched
tokens) that are its children; the median over the window's turns, in ms."""
from perf.harness import stats


def read(obs, spec):
    spans = obs.get("spans") or []
    inside = {}
    for name, _s, d, attrs in spans:
        if name in spec["program_spans"] and attrs \
                and attrs.get("parent_id") is not None:
            inside[attrs["parent_id"]] = inside.get(attrs["parent_id"], 0) + d
    host = [1e3 * (d - inside.get(attrs["span_id"], 0.0))
            for name, _s, d, attrs in spans
            if name == spec["loop_span"] and attrs and "span_id" in attrs]
    return stats.median(host) if host else None
