"""Operations and bytes of one decode step of the sliding-window / global
attention, routed-expert block (``perf/configs/mimo_v2_5_ep16.json``'s
family), computed from shapes.  The yardstick's, like ``flops.py``:
``step_roofline.serve_window_moe`` divides the least time
:func:`decode_step_cost` implies by the time the device took."""
from ..reference.mimo_v2 import GLOBAL, WINDOW, kv_heads, layers


def param_counts(cfg):
    """Parameters by what a decode step must read of them: one layer's
    attention matrices by kind, the dense MLP, one router (with its
    selection bias), one expert, the head; and the layers of each kind."""
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, vd = cfg["head_dim"], cfg["v_head_dim"]
    kinds = layers(cfg)

    def attention(kind):
        g = kv_heads(cfg, kind)
        return u * H * hd + u * g * (hd + vd) + H * vd * u

    E = cfg["published"]["n_routed_experts"]
    return {
        "attention_global": attention(GLOBAL),
        "attention_window": attention(WINDOW),
        "dense_mlp": 3 * u * cfg["intermediate_size"],
        "router": u * E + E,
        "expert": 3 * u * cfg["moe_intermediate_size"],
        "head": u * cfg["vocab_size"],
        "global_layers": sum(k == GLOBAL for k, _m in kinds),
        "window_layers": sum(k == WINDOW for k, _m in kinds),
        "dense_layers": sum(not m for _k, m in kinds),
        "expert_layers": sum(bool(m) for _k, m in kinds)}


def kv_bytes_per_token(cfg, kind, cache_bytes=2):
    """Bytes of ONE layer's keys and values of one token."""
    return kv_heads(cfg, kind) * (cfg["head_dim"] + cfg["v_head_dim"]) \
        * cache_bytes


def decode_step_cost(cfg, rows, context_tokens, experts_hit_per_layer,
                     held_assignments_per_step, weight_bytes=2,
                     router_bytes=4, cache_bytes=2):
    """One decode step of ``rows`` live sequences holding ``context_tokens``
    tokens each.  Bytes: every always-read weight once (attention of both
    kinds, the dense MLP, the routers in float32, the head; the ``rows``
    embedding rows gathered), each held expert that received a row once
    (``experts_hit_per_layer`` a layer, from the program's counter), every
    LIVE K/V row of the global layers read once, the live rings' tokens
    (the last ``min(context, window)``) of the window layers read once, and
    one new row a sequence and attention layer written.  FLOPs: two per
    always-read matrix weight per row, two per expert weight per held
    assignment, and attention's scores and context over what each kind
    reads."""
    n = param_counts(cfg)
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Lg, Lw, Le = n["global_layers"], n["window_layers"], n["expert_layers"]
    dense = Lg * n["attention_global"] + Lw * n["attention_window"] \
        + n["dense_layers"] * n["dense_mlp"] + n["head"]
    always = (dense + rows * u) * weight_bytes \
        + Le * n["router"] * router_bytes
    experts = experts_hit_per_layer * Le * n["expert"] * weight_bytes
    in_window = min(context_tokens, cfg["sliding_window"])
    kv = rows * (context_tokens + 1) * Lg \
        * kv_bytes_per_token(cfg, GLOBAL, cache_bytes)
    rings = rows * (in_window + 1) * Lw \
        * kv_bytes_per_token(cfg, WINDOW, cache_bytes)
    per_key = 2 * H * (cfg["head_dim"] + cfg["v_head_dim"])
    attention = per_key * (Lg * context_tokens + Lw * in_window)
    flops = rows * (2 * (dense + Le * n["router"]) + attention) \
        + 2 * held_assignments_per_step * n["expert"]
    return {"flops": float(flops),
            "bytes": float(always + experts + kv + rings),
            "always_read_bytes": float(always),
            "expert_bytes": float(experts),
            "kv_bytes": float(kv), "ring_bytes": float(rings)}
