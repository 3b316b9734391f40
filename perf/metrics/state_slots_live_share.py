"""Mean share of the cache's state slots (per-sequence recurrent state)
that a live sequence holds: ``state_slots_live`` over ``max_slots`` from
the cache's ``stats()``, noted by the system file at each of the driver's
2 Hz samples (beside ``kv_pages_live_share``) in a telemetry histogram,
whose sum over count is the mean."""


def read(obs, spec):
    h = (obs.get("histograms") or {}).get(spec["histogram"])
    return h["sum"] / h["count"] if h and h["count"] else None
