"""Operations and bytes of one decode step of the EVA-attention byte-level
block (``perf/configs/evabyte_pp2.json``), computed from shapes.  The
yardstick's, like ``flops.py``: ``step_roofline.serve_eva`` divides the least
time :func:`decode_step_cost` implies by the time the device took,
``eva_roofline`` does the same for EVA's own part (:func:`eva_step_cost`: the
ring, the summaries, the pooling) over the device time under its named
scopes.  The count is of LIVE bytes (ring entries ``<= position mod window``,
the summaries of closed windows), whatever the program reads: a program that
reads a whole ring and every reserved summary row moves more, and that is in
its time and not in the least time."""


def param_counts(cfg):
    """Parameters by what a decode step must read of them: one layer's four
    attention matrices, its SwiGLU, its two norms and two learned vectors a
    head (float32); the head of every prediction head, the final norm."""
    u, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"attention": 4 * u * u, "mlp": 3 * u * f,
            "layer_float32": 2 * u + 2 * u,      # norms; phi and mu, H x d
            "head": u * cfg["num_pred_heads"] * cfg["vocab_size"],
            "final_norm": u, "embedding": cfg["vocab_size"] * u,
            "layers": cfg["n_layer"]}


def entry_bytes(cfg, cache_bytes=2):
    """Bytes of ONE layer's keys and values of one position, or of one
    chunk's summary key and value."""
    return 2 * cfg["hidden_size"] * cache_bytes


def eva_step_cost(cfg, rows, ring_entries, summary_rows, cache_bytes=2):
    """EVA's own part of one decode step of ``rows`` live sequences that
    hold, between them, ``ring_entries`` live ring entries (``position mod
    window + 1`` a row) and ``summary_rows`` live summaries (those of closed
    windows), a layer.  Bytes a layer: every live entry and live summary
    read once, and a row's new entry written, its chunk's ``chunk_size``
    entries read again for the pooling and the chunk's summary written.
    FLOPs a layer: four a channel and column (scores and context), and the
    pooling's six a channel and chunk entry."""
    u, L, c = cfg["hidden_size"], cfg["n_layer"], cfg["chunk_size"]
    per = entry_bytes(cfg, cache_bytes)
    columns = ring_entries + summary_rows
    bytes_ = L * per * (columns + rows * (1 + c + 1))
    flops = L * u * (4 * columns + 6 * rows * c)
    return {"flops": float(flops), "bytes": float(bytes_),
            "ring_bytes": float(L * per * ring_entries),
            "summary_bytes": float(L * per * summary_rows)}


def decode_step_cost(cfg, rows, ring_entries, summary_rows, weight_bytes=2,
                     cache_bytes=2):
    """One decode step: every weight read once (16 layers' matrices, the
    eight heads' matrix, the ``rows`` embedding rows gathered; norms and the
    learned vectors float32), two FLOPs a matrix weight a row, plus EVA's
    :func:`eva_step_cost`."""
    n = param_counts(cfg)
    L = n["layers"]
    matrices = L * (n["attention"] + n["mlp"]) + n["head"]
    always = (matrices + rows * cfg["hidden_size"]) * weight_bytes \
        + (L * n["layer_float32"] + n["final_norm"]) * 4
    eva = eva_step_cost(cfg, rows, ring_entries, summary_rows, cache_bytes)
    return {"flops": float(2 * rows * matrices) + eva["flops"],
            "bytes": float(always) + eva["bytes"],
            "always_read_bytes": float(always), "eva_bytes": eva["bytes"],
            "ring_bytes": eva["ring_bytes"],
            "summary_bytes": eva["summary_bytes"]}
