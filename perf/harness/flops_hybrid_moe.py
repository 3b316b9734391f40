"""Operations and bytes of one decode step of the hybrid state-space /
attention / routed-expert block (``perf/configs/nemotron3_nano_ep8.json``'s
family), computed from shapes.  The yardstick's, like ``flops.py``:
``step_roofline.serve_hybrid`` divides the least time :func:`decode_step_cost`
implies by the time the device took, ``ssm_roofline`` does the same for the
Mamba-2 mixers alone (:func:`ssm_step_cost`) over the device time under their
named scopes."""
from ..reference.nemotron_h import pattern, sizes


def param_counts(cfg):
    """Parameters by what a decode step must read of them.  ``mamba`` and
    ``attention`` are one layer's matrices; ``mamba_small`` one layer's
    float32 vectors (convolution taps and bias, dt_bias, A_log, D, the
    gated norm's gain)."""
    z, u = sizes(cfg), cfg["hidden_size"]
    pat = pattern(cfg)
    return {
        "mamba": u * (2 * z["d_inner"] + 2 * z["G"] * z["N"] + z["H"])
        + z["d_inner"] * u,
        "mamba_small": z["conv_dim"] * (z["K"] + 1) + 3 * z["H"]
        + z["d_inner"],
        "attention": 2 * u * z["q_heads"] * z["hd"]
        + 2 * u * z["kv_heads"] * z["hd"],
        "router": u * cfg["published"]["n_routed_experts"],
        "shared": 2 * u * cfg["moe_shared_expert_intermediate_size"],
        "expert": 2 * u * cfg["moe_intermediate_size"],
        "head": u * cfg["vocab_size"],
        "mamba_layers": pat.count("M"), "attention_layers": pat.count("*"),
        "expert_layers": pat.count("E")}


def state_bytes_per_row(cfg, state_bytes=4, tail_bytes=2):
    """Bytes of ONE Mamba layer's per-sequence state: the recurrent state
    (float32) and the convolution's tail."""
    z = sizes(cfg)
    return z["H"] * z["P"] * z["N"] * state_bytes \
        + (z["K"] - 1) * z["conv_dim"] * tail_bytes


def ssm_step_cost(cfg, rows, weight_bytes=2):
    """The Mamba-2 mixers of one decode step of ``rows`` LIVE sequences:
    every mixer's weights read once, each live row's state read and written
    once a layer (what the program moves for padded rows is waste, not
    work).  FLOPs: two per matrix weight per row, and per row and layer the
    recurrence: decay, outer product and accumulate, and the read-out over
    the state, 5 operations an entry of the state."""
    n, z = param_counts(cfg), sizes(cfg)
    Lm = n["mamba_layers"]
    weights = Lm * (n["mamba"] * weight_bytes + n["mamba_small"] * 4)
    state = rows * Lm * state_bytes_per_row(cfg) * 2
    flops = rows * Lm * (2 * n["mamba"]
                         + 5 * z["H"] * z["P"] * z["N"]
                         + 2 * z["K"] * z["conv_dim"])
    return {"flops": float(flops), "bytes": float(weights + state),
            "weight_bytes": float(weights), "state_bytes": float(state)}


def decode_step_cost(cfg, rows, context_tokens, experts_hit_per_layer,
                     held_assignments_per_step, weight_bytes=2,
                     router_bytes=4, cache_bytes=2):
    """One decode step of ``rows`` live sequences holding ``context_tokens``
    tokens each.  Bytes: every always-read weight once (Mamba mixers,
    attention, routers in float32, shared experts, the head; the ``rows``
    embedding rows gathered), each held expert that received a row once
    (``experts_hit_per_layer`` a layer, from the program's counter), the
    live rows' recurrent state read and written once a Mamba layer, every
    live K/V row read once and one new row a sequence and attention layer
    written.  FLOPs: the mixers' (:func:`ssm_step_cost`), two per other
    always-read matrix weight per row, two per expert weight per held
    assignment, and attention's scores and context over the live rows."""
    n, z = param_counts(cfg), sizes(cfg)
    u = cfg["hidden_size"]
    ssm = ssm_step_cost(cfg, rows, weight_bytes)
    La, Le = n["attention_layers"], n["expert_layers"]
    dense = La * n["attention"] + Le * n["shared"] + n["head"]
    always = ssm["weight_bytes"] + (dense + rows * u) * weight_bytes \
        + Le * n["router"] * router_bytes
    experts = experts_hit_per_layer * Le * n["expert"] * weight_bytes
    kv = (rows * context_tokens + rows) * La * 2 * z["kv_heads"] * z["hd"] \
        * cache_bytes
    attention = La * z["q_heads"] * 4 * context_tokens * z["hd"]
    flops = ssm["flops"] + rows * (2 * (dense + Le * n["router"])
                                   + attention) \
        + 2 * held_assignments_per_step * n["expert"]
    return {"flops": float(flops),
            "bytes": float(always + experts + ssm["state_bytes"] + kv),
            "always_read_bytes": float(always),
            "expert_bytes": float(experts),
            "state_bytes": ssm["state_bytes"], "kv_bytes": float(kv)}
