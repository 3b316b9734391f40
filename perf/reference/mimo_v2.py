"""Plain reference of MiMo-V2.5's language model (the ``mimo_v2`` family's
layer as ``huggingface.co/XiaomiMiMo/MiMo-V2.5`` configures it): every layer
is ``h <- h + attn(RMSNorm(h))``, ``h <- h + mlp(RMSNorm(h))``, then a final
norm and an untied head.  ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no ring, no batching,
no kernel, a plain loop over the held experts, and nothing imported from
``mxnet_tpu``.

Attention, both kinds (``D`` = hidden, ``H`` = 64 query heads):

    q = u W_q (H x 192);  k = u W_k (n_kv x 192);  v = 0.707 u W_v (n_kv x 128)
    rotary, rotate-half pairing, on dimensions 0-63 of each head of q and k
        at the layer kind's base (1e7 global, 1e4 window); 64-191 untouched
    a_ij = q_i . k_j / sqrt(192);  query head h reads K/V head h // (H / n_kv)
    out = (H x 128) W_o

- **global** (``hybrid_layer_pattern`` 0): ``n_kv`` = 4, ``j <= i``, softmax.
- **window** (pattern 1): ``n_kv`` = 8, ``i - 127 <= j <= i``, and a learned
  sink bias ``s_h`` a query head as ONE MORE COLUMN of the softmax that
  carries no value: ``p_ij = exp(a_ij) / (exp(s_h) + sum_j' exp(a_ij'))``.

It is DENSE masked attention: every query scores every key of the sequence
and the mask decides, the definition.  Queries go 512 at a time so that the
``(heads, queries, keys)`` scores of a 4,608-token sequence fit beside the
weights; each query still sees all keys.

MLP: layer 0 (``moe_layer_freq`` 0) a dense SwiGLU ``(silu(u W_g) * (u
W_u)) W_d``; then ``s = sigmoid(u W_r)`` over all published experts, the 8
largest of ``s + b`` (``topk_method: noaux_tc``; ``n_group`` = ``topk_group``
= 1: no group is masked), ``w_k = s_k / (sum_chosen s + 1e-20)`` (the bias
chooses and does not weigh), ``sum_k w_k E_k(u)`` over the chosen experts
that are HELD; no shared expert.

It is given the same share of the deployment as the program
(``cfg["held_experts"]``, the sliced vocabulary, the first
``n_layer`` layers): what the absent experts would have added is
left out, here as in the program.

The weights are the reference's own, made from the seed one tensor at a time
(``shapes`` is the table the system file uses too) and kept as the
configuration stores them; a layer is widened to float32 when it is used.
Departures from the published model are under ``assumed`` in
``perf/configs/mimo_v2_5_ep16.json``.

``precision`` selects a lower precision or a broken mechanism, each put in
the program's place by ``served_token_gaps``: ``"weights_fp8"``, the cell's
control, rounds every matrix through e4m3 with one scale a tensor;
``"window_off"`` lets the window layers read the whole context (what a step
that ignores the ring's mask, or a cache that kept everything, would
serve); ``"sink_off"`` drops the sink's column.
"""
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
GLOBAL, WINDOW = 0, 1


def layers(cfg):
    """``[(attention kind, mlp kind)]`` of the layers this chip runs: the
    first ``n_layer`` of the published patterns."""
    n = cfg["n_layer"]
    return list(zip(cfg["hybrid_layer_pattern"][:n],
                    cfg["moe_layer_freq"][:n]))


def kv_heads(cfg, kind):
    return cfg["swa_num_key_value_heads"] if kind == WINDOW \
        else cfg["num_key_value_heads"]


def rotary_dim(cfg):
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def shapes(cfg):
    """The tensors of the share, by the published module names: ``{name:
    (shape, kind, dtype)}``.  Matrices are stored ``(in, out)`` and applied
    as ``x W``.  ``kind``: ``normal`` (N(0, initializer_range), rounded to
    bfloat16), ``ones``, ``sink`` (N(sink_bias.mean, sink_bias.std) a query
    head), ``select`` (N(0, selection_bias_std) an expert).  Experts are
    stacked ``(held, in, out)`` in the order of ``cfg["held_experts"]``."""
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, vd = cfg["head_dim"], cfg["v_head_dim"]
    E = cfg["published"]["n_routed_experts"]
    G, f = len(cfg["held_experts"]), cfg["moe_intermediate_size"]
    wt = cfg["precision"]["weights"]
    s = {"embed_tokens": ((cfg["vocab_size"], u), "normal", wt),
         "lm_head": ((u, cfg["vocab_size"]), "normal", wt),
         "norm": ((u,), "ones", "float32")}
    for i, (kind, moe) in enumerate(layers(cfg)):
        p, g = f"layers.{i}.", kv_heads(cfg, kind)
        s.update({
            p + "input_layernorm": ((u,), "ones", "float32"),
            p + "self_attn.q_proj": ((u, H * hd), "normal", wt),
            p + "self_attn.k_proj": ((u, g * hd), "normal", wt),
            p + "self_attn.v_proj": ((u, g * vd), "normal", wt),
            p + "self_attn.o_proj": ((H * vd, u), "normal", wt),
            p + "post_attention_layernorm": ((u,), "ones", "float32")})
        if kind == WINDOW:
            s[p + "self_attn.attention_sink_bias"] = ((H,), "sink", "float32")
        if moe:
            s.update({
                # the router's matrix is float32 (its scores are computed in
                # float32); its values are bfloat16-rounded
                p + "mlp.gate": ((u, E), "normal", "float32"),
                p + "mlp.gate.e_score_correction_bias": ((E,), "select",
                                                         "float32"),
                p + "mlp.experts.gate_proj": ((G, u, f), "normal", wt),
                p + "mlp.experts.up_proj": ((G, u, f), "normal", wt),
                p + "mlp.experts.down_proj": ((G, f, u), "normal", wt)})
        else:
            fd = cfg["intermediate_size"]
            s.update({p + "mlp.gate_proj": ((u, fd), "normal", wt),
                      p + "mlp.up_proj": ((u, fd), "normal", wt),
                      p + "mlp.down_proj": ((fd, u), "normal", wt)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype",
                                              "numbers"))
def _draw(key, shape, kind, dtype, numbers):
    std, sink_mean, sink_std, select_std = numbers
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "normal":
        x = (x * std).astype(jnp.bfloat16)
    elif kind == "sink":
        x = sink_mean + sink_std * x
    else:
        x = select_std * x
    return x.astype(dtype)


def weights(cfg, seed_key, device=None):
    """``{name: array}`` of :func:`shapes` from ``seed_key`` (a PRNG key of
    the run's seed): one tensor at a time, tensor ``i`` of the sorted names
    from ``fold_in(seed_key, i)``."""
    if device is not None:
        seed_key = jax.device_put(seed_key, device)
    numbers = (float(cfg["initializer_range"]),
               float(cfg["sink_bias"]["mean"]), float(cfg["sink_bias"]["std"]),
               float(cfg["selection_bias_std"]))
    out = {}
    for i, (name, (shape, kind, dtype)) in enumerate(sorted(
            shapes(cfg).items())):
        if kind == "ones":
            out[name] = jax.device_put(jnp.ones(shape, dtype), device)
        else:
            out[name] = _draw(jax.random.fold_in(seed_key, i), shape, kind,
                              dtype, numbers)
    return out


# ------------------------------------------------------------- the layers
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _fp8(x):
    """Round to what e4m3 holds (4 significant bits, subnormals below
    2**-6, largest 448) with one scale a tensor.  In arithmetic, not by
    converting to the 8-bit type and back: the chip's compiler drops such a
    pair of converts as excess precision."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / scale
    _m, e = jnp.frexp(y)                    # |y| in [2**(e-1), 2**e)
    step = jnp.exp2(jnp.maximum(e - 4, -9).astype(jnp.float32))
    return jnp.round(y / step) * step * scale


def _w(w, precision):
    """A stored matrix, widened to float32 (through e4m3 for the
    ``weights_fp8`` control)."""
    w = w.astype(jnp.float32)
    return _fp8(w) if precision == "weights_fp8" else w


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _expert(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def _rope(x, pos, base, rot):
    """``x (T, heads, head_dim)`` with its first ``rot`` dimensions rotated
    at ``pos (T,)``: dimension ``j`` pairs with ``j + rot / 2``
    (rotate-half), frequencies ``base ** (-2 j / rot)``."""
    half = rot // 2
    inv = jnp.asarray([base ** (-2.0 * j / rot) for j in range(half)],
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind", "precision"))
def _attention(w, h, *, cfg_key, kind, precision):
    """``h + Attention(RMSNorm(h))`` for one sequence ``h (T, U)``: dense,
    every query over every key, the mask deciding; the window layer's sink a
    column of the softmax."""
    z = dict(cfg_key)
    T = h.shape[0]
    H, hd, vd = z["num_attention_heads"], z["head_dim"], z["v_head_dim"]
    window = kind == WINDOW
    g = z["swa_kv_heads"] if window else z["kv_heads"]
    base = z["swa_rope_theta"] if window else z["rope_theta"]
    pos = jnp.arange(T, dtype=jnp.int32)
    a = _rms(h, w["input_layernorm"], z["eps"])
    q = _rope(_mm(a, _w(w["self_attn.q_proj"], precision)).reshape(T, H, hd),
              pos, base, z["rot"])
    k = _rope(_mm(a, _w(w["self_attn.k_proj"], precision)).reshape(T, g, hd),
              pos, base, z["rot"])
    v = z["value_scale"] * _mm(a, _w(w["self_attn.v_proj"], precision)
                               ).reshape(T, g, vd)
    k = jnp.repeat(k, H // g, axis=1)           # query head j: KV head j // r
    v = jnp.repeat(v, H // g, axis=1)

    def block(q_pos):
        qb, pb = q_pos                          # (Q, H, hd), (Q,)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        ok = pos[None, :] <= pb[:, None]
        if window and precision != "window_off":
            ok &= pos[None, :] > pb[:, None] - z["window"]
        s = jnp.where(ok[None], s, -jnp.inf)
        if window and precision != "sink_off":
            sink = jnp.broadcast_to(
                w["self_attn.attention_sink_bias"][:, None, None],
                s.shape[:2] + (1,))
            pr = jax.nn.softmax(jnp.concatenate([s, sink], -1), -1)[..., :-1]
        else:
            pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)

    Q = min(T, z["query_block"])
    if T % Q:
        raise ValueError(f"a sequence of {T} positions is not whole blocks "
                         f"of {Q} queries")
    o = jax.lax.map(block, (q.reshape(T // Q, Q, H, hd),
                            pos.reshape(T // Q, Q)))
    return h + _mm(o.reshape(T, H * vd), _w(w["self_attn.o_proj"], precision))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _dense_mlp(w, h, *, eps, precision):
    m = _rms(h, w["post_attention_layernorm"], eps)
    return h + _expert(m, _w(w["mlp.gate_proj"], precision),
                       _w(w["mlp.up_proj"], precision),
                       _w(w["mlp.down_proj"], precision))


def route(scores, bias, k):
    """``(ids (T, k), weights (T, k))`` over ``scores (T, E)``: the ``k``
    largest of ``scores + bias`` (no group is ever masked), ``weights = s_k
    / (sum_chosen s + 1e-20)`` of the scores WITHOUT the bias."""
    ids = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _route(w, h, *, cfg_key, precision):
    """``(m, ids, weights)`` of one expert layer over tokens ``h (T, U)``."""
    z = dict(cfg_key)
    m = _rms(h, w["post_attention_layernorm"], z["eps"])
    scores = jax.nn.sigmoid(_mm(m, _w(w["mlp.gate"], precision)))
    ids, wts = route(scores, w["mlp.gate.e_score_correction_bias"],
                     z["top_k"])
    return m, ids, wts * z["routed_scale"]


@functools.partial(jax.jit, static_argnames=("precision",))
def _add_expert(y, m, ids, wts, rows, live, e, wg, wu, wd, *, precision):
    """``y[rows] += w_e * E_e(m[rows])`` for the token ``rows`` that chose
    expert ``e`` (padded to a fixed count; ``live`` marks the real ones)."""
    w_e = jnp.where(ids[rows] == e, wts[rows], 0.0).sum(-1) * live
    out = _expert(m[rows], _w(wg, precision), _w(wu, precision),
                  _w(wd, precision))
    return y.at[rows].add(w_e[:, None] * out)


def _moe_mlp(w, h, *, cfg_key, precision):
    """``h + sum over the chosen experts held here of w_k E_k(m)``: a plain
    loop over the held experts, each applied to the tokens that chose it
    (their count padded to a power of two so that the shapes are few; a
    padded row adds zero)."""
    import numpy as np
    m, ids, wts = _route(w, h, cfg_key=cfg_key, precision=precision)
    chose = np.asarray(ids)
    y = h
    for j, e in enumerate(dict(cfg_key)["held_experts"]):
        rows = np.nonzero((chose == e).any(-1))[0]
        if not rows.size:
            continue
        n = min(max(8, 1 << int(rows.size - 1).bit_length()), chose.shape[0])
        padded = np.zeros((n,), "int32")
        padded[:rows.size] = rows
        y = _add_expert(
            y, m, ids, wts, jnp.asarray(padded),
            jnp.asarray(np.arange(n) < rows.size, jnp.float32),
            jnp.int32(e), w["mlp.experts.gate_proj"][j],
            w["mlp.experts.up_proj"][j], w["mlp.experts.down_proj"][j],
            precision=precision)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(norm, head, h, *, eps, precision):
    return _mm(_rms(h, norm, eps), _w(head, precision))


def _freeze(cfg, query_block):
    """The configuration's numbers as a hashable static argument."""
    scale = cfg["routed_scaling_factor"]
    out = {"num_attention_heads": cfg["num_attention_heads"],
           "head_dim": cfg["head_dim"], "v_head_dim": cfg["v_head_dim"],
           "kv_heads": cfg["num_key_value_heads"],
           "swa_kv_heads": cfg["swa_num_key_value_heads"],
           "rope_theta": float(cfg["rope_theta"]),
           "swa_rope_theta": float(cfg["swa_rope_theta"]),
           "rot": rotary_dim(cfg), "window": cfg["sliding_window"],
           "value_scale": cfg["attention_value_scale"],
           "eps": cfg["layernorm_epsilon"],
           "top_k": cfg["num_experts_per_tok"],
           "routed_scale": 1.0 if scale is None else float(scale),
           "held_experts": tuple(cfg["held_experts"]),
           "query_block": int(query_block)}
    return tuple(sorted(out.items()))


def forward(w, cfg, tokens, precision="float32", query_block=512):
    """Logits ``(T, vocab)`` of one sequence ``tokens (T,)``: row ``t``
    scores the token that follows position ``t``.  A layer at a time."""
    key, eps = _freeze(cfg, query_block), cfg["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        h = w["embed_tokens"][tokens].astype(jnp.float32)
        for i, (kind, moe) in enumerate(layers(cfg)):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            h = _attention(lw, h, cfg_key=key, kind=kind,
                           precision=precision)
            if moe:
                h = _moe_mlp(lw, h, cfg_key=key, precision=precision)
            else:
                h = _dense_mlp(lw, h, eps=eps, precision=precision)
        return _head(w["norm"], w["lm_head"], h, eps=eps,
                     precision=precision)


@jax.jit
def _gaps(ref, chosen):
    return ref.max(axis=-1) - jnp.take_along_axis(
        ref, chosen[:, None], axis=-1)[:, 0]


def served_token_gaps(w, cfg, prompts, served, pad_to, precision="float32"):
    """For finished requests: at each served position, how far the
    reference's logit of the served token lies below the reference's best
    (0 where the served token IS the reference's choice).

    With ``precision`` set to a lower one (or a broken mechanism), the token
    read is not the served one but the token that it puts first at that
    position (teacher-forced on the same prompt and served tokens): the
    control.  One sequence at a time, padded to the next multiple of
    ``pad_to``'s ninth (512 of 4,608; every layer is causal, so padding
    touches nothing before it).  Returns one float32 array of gaps over all
    served tokens, request after request."""
    import numpy as np
    step = max(pad_to // 9, 1)
    out = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        n = -(-len(seq) // step) * step
        padded = np.zeros((n,), "int32")
        padded[:len(seq)] = seq
        chosen = np.zeros((n,), "int32")
        # row t scores the token that follows position t
        lo, hi = len(prompt) - 1, len(seq) - 1
        chosen[lo:hi] = tokens
        ref = forward(w, cfg, jnp.asarray(padded), query_block=step)
        if precision != "float32":
            chosen = jnp.argmax(forward(w, cfg, jnp.asarray(padded),
                                        precision, query_block=step), axis=-1)
        out.append(np.asarray(_gaps(ref, jnp.asarray(chosen)),
                              "float32")[lo:hi])
    return np.concatenate(out) if out else np.zeros((0,), "float32")
