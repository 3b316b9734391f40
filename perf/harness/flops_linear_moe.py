"""Operations and bytes of one decode step of the linear-attention / gated
grouped-query attention, routed-expert block
(``perf/configs/solar_open2_ep16.json``'s family), computed from shapes.  The
yardstick's, like ``flops.py``: ``step_roofline.serve_linear_moe`` divides
the least time :func:`decode_step_cost` implies by the time the device took,
``kda_roofline`` does the same for the KDA mixers alone
(:func:`kda_step_cost`) over the device time under their named scopes."""
from ..reference.solar_open2 import gqa_layers, sizes


def param_counts(cfg):
    """Parameters by what a decode step must read of them.  ``kda`` and
    ``attention`` are one layer's matrices; ``kda_small`` one layer's
    float32 vectors (the three convolutions' taps, A_log, dt_bias, the output
    gate's bias, the head norm's gain)."""
    z, u = sizes(cfg), cfg["hidden_size"]
    kw, r = z["kw"], z["rank"]
    qw, kvw = z["q_heads"] * z["hd"], z["kv_heads"] * z["hd"]
    n_gqa = len(gqa_layers(cfg))
    return {
        "kda": 4 * u * kw + 2 * (u * r + r * kw) + u * z["H"],
        "kda_small": 3 * kw * z["K"] + z["H"] + 2 * kw + z["dk"],
        "attention": 3 * u * qw + 2 * u * kvw,
        "router": u * cfg["published"]["n_routed_experts"],
        "shared": 3 * u * z["f"],
        "expert": 3 * u * z["f"],
        "head": u * cfg["vocab_size"],
        "kda_layers": cfg["n_layer"] - n_gqa, "attention_layers": n_gqa,
        "expert_layers": cfg["n_layer"]}


def state_bytes_per_row(cfg, state_bytes=4, tail_bytes=2):
    """Bytes of ONE KDA layer's per-sequence state: the matrix state a head
    (float32) and the three convolutions' tails."""
    z = sizes(cfg)
    return z["H"] * z["dk"] * z["dk"] * state_bytes \
        + (z["K"] - 1) * 3 * z["kw"] * tail_bytes


def kda_step_cost(cfg, rows, weight_bytes=2):
    """The KDA mixers of one decode step of ``rows`` LIVE sequences: every
    mixer's weights read once, each live row's state (matrix and tails) read
    and written once a layer (what the program moves for padded rows is
    waste, not work).  FLOPs: two per matrix weight per row, and per row and
    layer the recurrence: the decay, ``S^T k``, the rank-one update and the
    read-out ``S^T q``, 7 operations an entry of the state, and the three
    convolutions."""
    n, z = param_counts(cfg), sizes(cfg)
    Lk = n["kda_layers"]
    weights = Lk * (n["kda"] * weight_bytes + n["kda_small"] * 4)
    state = rows * Lk * state_bytes_per_row(cfg) * 2
    flops = rows * Lk * (2 * n["kda"] + 7 * z["H"] * z["dk"] * z["dk"]
                         + 2 * z["K"] * 3 * z["kw"])
    return {"flops": float(flops), "bytes": float(weights + state),
            "weight_bytes": float(weights), "state_bytes": float(state)}


def decode_step_cost(cfg, rows, context_tokens, experts_hit_per_layer,
                     held_assignments_per_step, weight_bytes=2,
                     router_bytes=4, cache_bytes=2):
    """One decode step of ``rows`` live sequences holding ``context_tokens``
    tokens each.  Bytes: every always-read weight once (KDA mixers,
    grouped-query attention with its gate, routers in float32, shared
    experts, the head; the ``rows`` embedding rows gathered), each held
    expert that received a row once (``experts_hit_per_layer`` a layer, from
    the program's counter), the live rows' state read and written once a
    KDA layer, every live K/V row read once and one new row a sequence and
    attention layer written.  FLOPs: the mixers' (:func:`kda_step_cost`),
    two per other always-read matrix weight per row, two per expert weight
    per held assignment, and attention's scores and context over the live
    rows."""
    n, z = param_counts(cfg), sizes(cfg)
    u = cfg["hidden_size"]
    kda = kda_step_cost(cfg, rows, weight_bytes)
    La, Le = n["attention_layers"], n["expert_layers"]
    dense = La * n["attention"] + Le * n["shared"] + n["head"]
    always = kda["weight_bytes"] + (dense + rows * u) * weight_bytes \
        + Le * n["router"] * router_bytes
    experts = experts_hit_per_layer * Le * n["expert"] * weight_bytes
    kv = (rows * context_tokens + rows) * La * 2 * z["kv_heads"] * z["hd"] \
        * cache_bytes
    attention = La * z["q_heads"] * 4 * context_tokens * z["hd"]
    flops = kda["flops"] + rows * (2 * (dense + Le * n["router"])
                                   + attention) \
        + 2 * held_assignments_per_step * n["expert"]
    return {"flops": float(flops),
            "bytes": float(always + experts + kda["state_bytes"] + kv),
            "always_read_bytes": float(always),
            "expert_bytes": float(experts),
            "state_bytes": kda["state_bytes"], "kv_bytes": float(kv)}
