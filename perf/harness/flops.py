"""Operations and bytes the algorithms need, computed from shapes.  These
are the yardstick's: a roofline share divides the least time they imply by
the time the device took."""


def gpt2_param_count(cfg):
    u, hid = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    per_layer = u * 3 * u + 3 * u + u * u + u + u * hid + hid + hid * u + u \
        + 4 * u
    return {"embedding": cfg["vocab_size"] * u,
            "positions": cfg["n_positions"] * u,
            "layers": cfg["n_layer"] * per_layer, "final_ln": 2 * u}


def decode_step_cost(cfg, rows, context_tokens, weight_bytes, kv_bytes):
    """One decode step of ``rows`` sequences holding ``context_tokens`` live
    tokens each: every weight is read once (the tied head reads the whole
    embedding; the ``rows`` embedding rows gathered are inside it), every
    live key and value is read once, one new key and value per row and layer
    is written.  FLOPs: two per weight of the matrices and the head per row,
    four per live token per channel per layer per row for attention."""
    n = gpt2_param_count(cfg)
    u, L = cfg["n_embd"], cfg["n_layer"]
    weights = n["embedding"] + n["layers"] + n["final_ln"] + rows * u
    kv_read = rows * context_tokens * 2 * L * u
    kv_write = rows * 2 * L * u
    flops = rows * (2 * (n["layers"] + n["embedding"])
                    + 4 * context_tokens * u * L)
    return {"flops": float(flops),
            "bytes": float(weights * weight_bytes
                           + (kv_read + kv_write) * kv_bytes)}


def bert_step_flops(cfg, rows, seq_len):
    """Model FLOPs of one training step, forward plus backward (three times
    the forward's matrix products; no recomputation counted): the encoder's
    matrices on every token, attention's two T x T products per layer, the
    pooler and next-sentence head on one token a row, and the masked-LM
    head on the masked positions only."""
    u, hid = cfg["hidden_size"], cfg["intermediate_size"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    m = cfg["max_predictions_per_seq"]
    tokens = rows * seq_len
    encoder = 2 * tokens * L * (4 * u * u + 2 * u * hid)
    attention = 2 * rows * L * 2 * seq_len * seq_len * u
    heads = 2 * rows * (u * u + 2 * u) + 2 * rows * m * (u * u + u * V)
    return 3.0 * (encoder + attention + heads)


def least_seconds(cost, peaks, chips=1):
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two it is."""
    t_flops = cost["flops"] / (peaks["bf16_flops_per_s"] * chips)
    t_bytes = cost.get("bytes", 0.0) / (peaks["hbm_bytes_per_s"] * chips)
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
