"""Plain reference of A.X-K1's forward pass (the DeepSeek-V3 family's layer
as ``huggingface.co/skt/A.X-K1`` configures it): RMSNorm, latent (MLA)
attention in its EXPANDED form, YaRN rotary positions on a 64-wide slice,
a dense SwiGLU layer and then routed + shared experts, an untied head.
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``;
no cache, no batching, no kernel, a plain loop over the held experts (each
applied to the tokens that chose it), and nothing imported from
``mxnet_tpu``.

It is given the same share of the deployment as the program
(``cfg["held_experts"]``, the sliced vocabulary): the router scores and
chooses over all ``published.n_routed_experts`` experts, the sum runs over
the chosen experts that are held, and what the absent experts would have
added is left out — here as in the program.

The weights are the reference's own, made from the seed one tensor at a
time and kept as the configuration stores them (matrices rounded to
bfloat16; the router's matrix and the norms' gains float32, the router's
values bfloat16-rounded); the forward widens one layer at a time to
float32, so 7 GB of stored weights never become 14.

Departures from the published model, each also under ``assumed`` in
``perf/configs/axk1_ep16.json``: ``topk_method: "none"`` is read as the
family's group-limited choice WITHOUT a score-correction bias; the rotary
pairing is the family's (interleaved pairs, listed first members then
second); weights are N(0, 0.02) and gains 1.

``precision`` selects the lower-precision controls of the correctness
check: ``"weights_fp8"`` rounds every matrix through e4m3 with one scale a
tensor, ``"latent_fp8"`` rounds each token's latent row ``(c_kv | k_r)``
through e4m3 with one scale a row — what an fp8 weight store, or an fp8
latent cache, would hand the layer.
"""
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def shapes(cfg):
    """The tensors of the share, by the published module names:
    ``{name: (shape, kind, dtype)}``.  Matrices are stored ``(in, out)`` and
    applied as ``x W``; ``kind`` is ``normal`` (N(0, initializer_range)) or
    ``ones``.  Experts are stacked ``(held, in, out)`` in the order of
    ``cfg["held_experts"]``."""
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    G, f = len(cfg["held_experts"]), cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    wt = cfg["precision"]["weights"]
    s = {"embed_tokens": ((cfg["vocab_size"], u), "normal", wt),
         "lm_head": ((u, cfg["vocab_size"]), "normal", wt),
         "norm": ((u,), "ones", "float32")}
    for i in range(cfg["n_layer"]):
        p = f"layers.{i}."
        s.update({
            p + "input_layernorm": ((u,), "ones", "float32"),
            p + "self_attn.q_a_proj": ((u, ql), "normal", wt),
            p + "self_attn.q_a_layernorm": ((ql,), "ones", "float32"),
            p + "self_attn.q_b_proj": ((ql, H * (nope + rope)), "normal", wt),
            p + "self_attn.kv_a_proj_with_mqa": ((u, kl + rope), "normal",
                                                 wt),
            p + "self_attn.kv_a_layernorm": ((kl,), "ones", "float32"),
            p + "self_attn.kv_b_proj": ((kl, H * (nope + vd)), "normal", wt),
            p + "self_attn.o_proj": ((H * vd, u), "normal", wt),
            p + "post_attention_layernorm": ((u,), "ones", "float32")})
        if i < cfg["first_k_dense_replace"]:
            fd = cfg["intermediate_size"]
            s.update({p + "mlp.gate_proj": ((u, fd), "normal", wt),
                      p + "mlp.up_proj": ((u, fd), "normal", wt),
                      p + "mlp.down_proj": ((fd, u), "normal", wt)})
        else:
            s.update({
                # the router's matrix is float32 (the family computes its
                # scores in float32); its values are bfloat16-rounded
                p + "mlp.gate": ((u, cfg["published"]["n_routed_experts"]),
                                 "normal", "float32"),
                p + "mlp.experts.gate_proj": ((G, u, f), "normal", wt),
                p + "mlp.experts.up_proj": ((G, u, f), "normal", wt),
                p + "mlp.experts.down_proj": ((G, f, u), "normal", wt),
                p + "mlp.shared_experts.gate_proj": ((u, fs), "normal", wt),
                p + "mlp.shared_experts.up_proj": ((u, fs), "normal", wt),
                p + "mlp.shared_experts.down_proj": ((fs, u), "normal", wt)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    x = (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)
    return x.astype(dtype)


def weights(cfg, seed_key, device=None):
    """``{name: array}`` of :func:`shapes` from ``seed_key`` (a PRNG key of
    the run's seed): one tensor at a time, tensor ``i`` of the sorted names
    from ``fold_in(seed_key, i)``, every normal rounded to bfloat16."""
    if device is not None:
        seed_key = jax.device_put(seed_key, device)
    out = {}
    for i, (name, (shape, kind, dtype)) in enumerate(sorted(
            shapes(cfg).items())):
        if kind == "normal":
            out[name] = _normal(jax.random.fold_in(seed_key, i), shape,
                                float(cfg["initializer_range"]), dtype)
        else:
            out[name] = jax.device_put(jnp.ones(shape, dtype), device)
    return out


# ------------------------------------------------------------- the rotary
def yarn_inv_freq(cfg):
    """Inverse frequencies of the ``qk_rope_head_dim`` / 2 rotary pairs,
    YaRN as the family computes it (``DeepseekV3YarnRotaryEmbedding``):
    ``1/f`` and ``1/(factor f)`` blended by a linear ramp over the pair
    index between the two correction dims."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    freq = [base ** (-(2 * j) / dim) for j in range(dim // 2)]

    def correction(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for j, f in enumerate(freq):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f / rs["factor"] * ramp + f * (1.0 - ramp))
    return out


def softmax_scale(cfg):
    """``(nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 mscale_all_dim
    ln(factor) + 1``; with ``mscale == mscale_all_dim`` cos and sin are not
    scaled."""
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(x, pos, inv_freq):
    """``x (T, ..., rope)`` rotated at ``pos (T,)``: pair ``j`` is
    ``(x[2j], x[2j+1])``; the result lists first members, then second."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq,
                                                         jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ------------------------------------------------------------- the layers
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _fp8(x, axes=None):
    """Round to what e4m3 holds (4 significant bits, subnormals below
    2**-6, largest 448) with one scale over ``axes`` (None: the whole
    tensor).  In arithmetic, not by converting to the 8-bit type and back:
    the chip's compiler drops such a pair of converts as excess precision,
    and the control then reads exactly 0."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
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


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(w, h, pos, *, cfg_key, precision):
    """``h + Attention(RMSNorm(h))`` for one sequence ``h (T, U)``."""
    cfg = dict(cfg_key)
    T = h.shape[0]
    H, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kl, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq = cfg["inv_freq"]
    a = _rms(h, w["input_layernorm"], eps)
    cq = _rms(_mm(a, _w(w["self_attn.q_a_proj"], precision)),
              w["self_attn.q_a_layernorm"], eps)
    q = _mm(cq, _w(w["self_attn.q_b_proj"], precision)).reshape(
        T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, inv_freq)
    ckv_kr = _mm(a, _w(w["self_attn.kv_a_proj_with_mqa"], precision))
    ckv = _rms(ckv_kr[:, :kl], w["self_attn.kv_a_layernorm"], eps)
    kr = _rope(ckv_kr[:, kl:], pos, inv_freq)
    if precision == "latent_fp8":
        row = _fp8(jnp.concatenate([ckv, kr], -1), axes=-1)
        ckv, kr = row[:, :kl], row[:, kl:]
    kv = _mm(ckv, _w(w["self_attn.kv_b_proj"], precision)).reshape(
        T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HI)
         + jnp.einsum("qhr,kr->hqk", q_rope, kr, precision=HI)) \
        * cfg["softmax_scale"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(T, H * vd)
    return h + _mm(o, _w(w["self_attn.o_proj"], precision))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _dense_ffn(w, h, *, eps, precision):
    m = _rms(h, w["post_attention_layernorm"], eps)
    return h + _expert(m, _w(w["mlp.gate_proj"], precision),
                       _w(w["mlp.up_proj"], precision),
                       _w(w["mlp.down_proj"], precision))


def route(scores, cfg):
    """The group-limited choice over ``scores (T, E)``: ``(ids (T, k),
    weights (T, k))`` with ``weights = routed_scaling_factor * s_k /
    sum_chosen s``."""
    T, E = scores.shape
    n_group, k = cfg["n_group"], cfg["num_experts_per_tok"]
    per = E // n_group
    group = jnp.sort(scores.reshape(T, n_group, per), axis=-1)[..., -2:] \
        .sum(-1)
    best = jnp.argsort(-group, axis=-1, stable=True)[:, :cfg["topk_group"]]
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), scores, 0.0)
    ids = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, cfg["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _route_and_shared(w, h, *, cfg_key, precision):
    """``(m, ids, weights, h + E_shared(m))`` of one expert layer."""
    cfg = dict(cfg_key)
    m = _rms(h, w["post_attention_layernorm"], cfg["rms_norm_eps"])
    scores = jax.nn.sigmoid(_mm(m, _w(w["mlp.gate"], precision)))
    ids, wts = route(scores, cfg)
    y = _expert(m, _w(w["mlp.shared_experts.gate_proj"], precision),
                _w(w["mlp.shared_experts.up_proj"], precision),
                _w(w["mlp.shared_experts.down_proj"], precision))
    return m, ids, wts, h + y


@functools.partial(jax.jit, static_argnames=("precision",))
def _add_expert(y, m, ids, wts, rows, live, e, wg, wu, wd, *, precision):
    """``y[rows] += w_e * E_e(m[rows])`` for the token ``rows`` that chose
    expert ``e`` (padded to a fixed count; ``live`` marks the real ones)."""
    w_e = jnp.where(ids[rows] == e, wts[rows], 0.0).sum(-1) * live
    out = _expert(m[rows], _w(wg, precision), _w(wu, precision),
                  _w(wd, precision))
    return y.at[rows].add(w_e[:, None] * out)


def _moe_ffn(w, h, *, cfg_key, precision):
    """``h + sum over the chosen experts held here of w_k E_k(m) +
    E_shared(m)``: a plain loop over the held experts, each applied to the
    tokens that chose it (their count padded to a power of two so that the
    shapes are few; a padded row adds zero)."""
    import numpy as np
    m, ids, wts, y = _route_and_shared(w, h, cfg_key=cfg_key,
                                       precision=precision)
    chose = np.asarray(ids)
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


def _freeze(cfg):
    """The configuration's numbers as a hashable static argument."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "n_group",
            "topk_group", "num_experts_per_tok", "routed_scaling_factor")
    out = {k: cfg[k] for k in keys}
    out["held_experts"] = tuple(cfg["held_experts"])
    out["inv_freq"] = tuple(yarn_inv_freq(cfg))
    out["softmax_scale"] = softmax_scale(cfg)
    return tuple(sorted(out.items()))


def forward(w, cfg, tokens, precision="float32"):
    """Logits ``(T, vocab)`` of one sequence ``tokens (T,)``: row ``t``
    scores the token that follows position ``t``.  A layer at a time."""
    key, eps = _freeze(cfg), cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = w["embed_tokens"][tokens].astype(jnp.float32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        for i in range(cfg["n_layer"]):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            h = _attention(lw, h, pos, cfg_key=key, precision=precision)
            if i < cfg["first_k_dense_replace"]:
                h = _dense_ffn(lw, h, eps=eps, precision=precision)
            else:
                h = _moe_ffn(lw, h, cfg_key=key, precision=precision)
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

    With ``precision`` set to a lower one, the token read is not the served
    one but the token that the lower precision puts first at that position
    (teacher-forced on the same prompt and served tokens): the control.
    One sequence at a time, padded to the next multiple of ``pad_to``'s
    quarter (causal, so padding touches nothing before it).  Returns one
    float32 array of gaps over all served tokens, request after request."""
    import numpy as np
    step = max(pad_to // 4, 1)
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
        ref = forward(w, cfg, jnp.asarray(padded))
        if precision != "float32":
            chosen = jnp.argmax(forward(w, cfg, jnp.asarray(padded),
                                        precision), axis=-1)
        out.append(np.asarray(_gaps(ref, jnp.asarray(chosen)),
                              "float32")[lo:hi])
    return np.concatenate(out) if out else np.zeros((0,), "float32")
