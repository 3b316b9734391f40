"""Compiles inside the serving window: XLA backend compiles the harness
counted plus the decode runtime's own ``decode.compile_miss`` counter."""


def read(obs, spec):
    n = (obs.get("values") or {}).get("compiles_in_window")
    if n is None:
        return None
    return n + (obs.get("counters") or {}).get(spec["miss_counter"], 0)
