"""Share of the scheduler loop's device-facing time spent in prefill: the
seconds of the runtime's ``decode.prefill`` spans over those plus the sum of
the scheduler's ``decode.step_ms`` histogram.  (The runtime's ``decode.step``
span closes before the step's tokens are fetched, so it times the dispatch
only; the scheduler's histogram brackets the whole step.)"""


def read(obs, spec):
    prefill = sum(d for name, _s, d, attrs in obs.get("spans") or []
                  if name == spec["prefill_span"]
                  and all(k in (attrs or {}) for k in spec["has_attrs"]))
    hist = (obs.get("histograms") or {}).get(spec["step_histogram"])
    if not hist or not hist["count"]:
        return None
    steps = hist["sum"] / 1e3
    return 100.0 * prefill / (prefill + steps)
