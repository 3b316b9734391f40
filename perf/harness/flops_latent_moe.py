"""Operations and bytes of one decode step of the latent-attention,
routed-expert block (``perf/configs/axk1_ep16.json``'s family), computed
from shapes.  The yardstick's, like ``flops.py``: ``step_roofline.serve_moe``
divides the least time they imply by the time the device took."""


def param_counts(cfg):
    """Parameters by what a decode step must read of them."""
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    L, dense = cfg["n_layer"], cfg["first_k_dense_replace"]
    f = cfg["moe_intermediate_size"]
    attention = u * ql + ql * H * (nope + rope) + u * (kl + rope) \
        + kl * H * (nope + vd) + H * vd * u
    return {"attention": L * attention,
            "dense_ffn": dense * 3 * u * cfg["intermediate_size"],
            "router": (L - dense) * u * cfg["published"]["n_routed_experts"],
            "shared": (L - dense) * 3 * u * f * cfg["n_shared_experts"],
            "expert": 3 * u * f,
            "head": u * cfg["vocab_size"],
            "expert_layers": L - dense}


def decode_step_cost(cfg, rows, context_tokens, experts_hit_per_layer,
                     held_assignments_per_step, weight_bytes=2,
                     router_bytes=4, cache_bytes=2):
    """One decode step of ``rows`` sequences holding ``context_tokens`` live
    tokens each.  Bytes: every always-read weight once (attention, dense
    FFN, routers in float32, shared experts, the head; the ``rows``
    embedding rows gathered), each held expert that received a row once
    (``experts_hit_per_layer`` a layer, from the program's counter), every
    live latent row once and one new row a sequence and layer.  FLOPs: two
    per always-read matrix weight per row, two per expert weight per held
    assignment, and absorbed attention per row, head and layer: the
    query's fold (nope x latent), scores and context over the live rows
    (latent + rope, latent), the output's unfold (latent x v)."""
    n = param_counts(cfg)
    u, H, L = cfg["hidden_size"], cfg["num_attention_heads"], cfg["n_layer"]
    kl, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    row = kl + rope
    always = n["attention"] + n["dense_ffn"] + n["shared"] + n["head"]
    weights = (always + rows * u) * weight_bytes \
        + n["router"] * router_bytes \
        + experts_hit_per_layer * n["expert_layers"] * n["expert"] \
        * weight_bytes
    cache = (rows * context_tokens + rows) * L * row * cache_bytes
    attention = L * H * (2 * cfg["qk_nope_head_dim"] * kl
                         + 2 * context_tokens * (row + kl)
                         + 2 * kl * cfg["v_head_dim"])
    flops = rows * (2 * (always + n["router"]) + attention) \
        + 2 * held_assignments_per_step * n["expert"]
    return {"flops": float(flops), "bytes": float(weights + cache),
            "always_read_bytes": float(always * weight_bytes
                                       + n["router"] * router_bytes)}
