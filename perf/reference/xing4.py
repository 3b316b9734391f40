"""Plain reference of Xing4.0-29B-A4B's forward pass
(``huggingface.co/XingChen-AGI/Xing4.0-29B-A4B``, ``model_type: xing4_0``):
the DeepSeek-V3 family's latent (MLA) attention in its EXPANDED form, YaRN
rotary positions, two leading dense SwiGLU layers and then routed + shared
experts chosen with the ``noaux_tc`` score-correction bias, all on a residual
path of ``hc_mult`` streams mixed by manifold-constrained hyper-connections
(mHC, arXiv:2512.24880).  ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no batching, no
kernel, a plain loop over the held experts, and nothing imported from
``mxnet_tpu``.  The MLA and expert arithmetic that is the family's is
``perf/reference/axk1.py``'s helpers (norm, rotary, YaRN numbers, the e4m3
rounding, an expert's product chain); the sublayers are written here because
here none of them adds to "the" hidden state.

The residual path, for a token's stream ``X (n, C)`` and each of the 2 x
layers sublayers ``s`` with its ``phi (nC, n + n + n*n)`` (columns pre | post
| res), ``alpha = (a_pre, a_post, a_res)`` and ``bias``::

    x  = vec(X);  x' = x * rsqrt(mean(x^2) + hc_eps)
    Hpre  = sigmoid(a_pre  * (x' phi_pre)  + b_pre)
    Hpost = 2 sigmoid(a_post * (x' phi_post) + b_post)
    M = exp(clip(a_res * mat(x' phi_res) + b_res, clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M = M / (colsum(M) + hc_eps)
                              M = M / (rowsum(M) + hc_eps)
    u  = Hpre X;   y = f_s(RMSNorm_s(u));   X' = M X + Hpost^T y

``X_0`` is ``n`` copies of the embedding; after the last layer the streams
are summed, normed and multiplied by the head.  It is given the same share
of the deployment as the program (``cfg["held_experts"]``, the sliced
vocabulary), as ``axk1.py`` is.

What the config does not say is under ``assumed`` in
``perf/configs/xing4_29b_ep8.json``; ``num_nextn_predict_layers`` (the
multi-token-prediction layer) is left out: the main model's logits do not
depend on it.

``precision`` selects the controls of the correctness check, each of which
must fail it: ``"weights_fp8"`` rounds every bfloat16 matrix through e4m3
with one scale a tensor; ``"sinkhorn_off"`` runs no Sinkhorn round (``Hres =
M / rowsum(M)``); ``"hc_static"`` sets ``a_pre = a_post = a_res = 0``, so the
coefficients no longer depend on the token.
"""
import functools

import jax
import jax.numpy as jnp

from . import axk1
from .axk1 import (HI, _add_expert, _expert, _gaps, _head, _mm, _normal,
                   _rms, _rope, _w, softmax_scale, yarn_inv_freq)

SUBLAYERS = ("hc_attn", "hc_ffn")


def shapes(cfg):
    """The tensors of the share, by the published module names: the
    family's (``axk1.shapes``) and, a layer, the two sublayers' ``hc_*.phi``
    / ``.alpha`` / ``.bias`` (float32) and an expert layer's
    ``mlp.gate.e_score_correction_bias``.  ``{name: (shape, kind, dtype)}``;
    the kinds beyond ``axk1``'s: ``hc_phi`` (N(0, hc_init.phi_std), float32
    as drawn), ``hc_alpha`` (the three scalars ``hc_init.alpha``),
    ``hc_bias`` (zeros, ``hc_init.res_diagonal`` on the diagonal of
    ``b_res``), ``select`` (N(0, selection_bias_std) an expert)."""
    n, u = cfg["hc_mult"], cfg["hidden_size"]
    s = axk1.shapes(cfg)
    for i in range(cfg["n_layer"]):
        p = f"layers.{i}."
        for sub in SUBLAYERS:
            s[p + sub + ".phi"] = ((n * u, n * (n + 2)), "hc_phi", "float32")
            s[p + sub + ".alpha"] = ((3,), "hc_alpha", "float32")
            s[p + sub + ".bias"] = ((n * (n + 2),), "hc_bias", "float32")
        if i >= cfg["first_k_dense_replace"]:
            s[p + "mlp.gate.e_score_correction_bias"] = (
                (cfg["published"]["n_routed_experts"],), "select", "float32")
    return s


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal_f32(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def weights(cfg, seed_key, device=None):
    """``{name: array}`` of :func:`shapes` from ``seed_key``: one tensor at
    a time, tensor ``i`` of the sorted names from ``fold_in(seed_key, i)``;
    the family's normals rounded to bfloat16, the mHC parameters and the
    selection bias float32."""
    import numpy as np
    if device is not None:
        seed_key = jax.device_put(seed_key, device)
    n, init = cfg["hc_mult"], cfg["hc_init"]
    bias = np.zeros((n * (n + 2),), "float32")
    bias[2 * n:] = (float(init["res_diagonal"]) * np.eye(n)).reshape(-1)
    fixed = {"hc_alpha": np.asarray(init["alpha"], "float32"),
             "hc_bias": bias}
    out = {}
    for i, (name, (shape, kind, dtype)) in enumerate(sorted(
            shapes(cfg).items())):
        key = jax.random.fold_in(seed_key, i)
        if kind == "normal":
            out[name] = _normal(key, shape, float(cfg["initializer_range"]),
                                dtype)
        elif kind == "hc_phi":
            out[name] = _normal_f32(key, shape, float(init["phi_std"]))
        elif kind == "select":
            out[name] = _normal_f32(key, shape,
                                    float(cfg["selection_bias_std"]))
        elif kind == "ones":
            out[name] = jax.device_put(jnp.ones(shape, dtype), device)
        else:
            out[name] = jax.device_put(jnp.asarray(fixed[kind], dtype),
                                       device)
    return out


# ------------------------------------------------------- the residual path
def coefficients(hc, X, cfg, precision="float32"):
    """``(Hpre (T, n), Hpost (T, n), Hres (T, n, n))`` of one sublayer for
    streams ``X (T, n, C)``; ``hc`` is the sublayer's ``{"phi", "alpha",
    "bias"}``."""
    T, n, C = X.shape
    eps = cfg["hc_eps"]
    x = X.reshape(T, n * C)
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    z = _mm(xn, hc["phi"])
    alpha = hc["alpha"] * (0.0 if precision == "hc_static" else 1.0)
    b = hc["bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    res = alpha[2] * z[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n)
    m = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    if precision == "sinkhorn_off":
        return h_pre, h_post, m / m.sum(-1, keepdims=True)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + eps)        # columns
        m = m / (m.sum(-1, keepdims=True) + eps)        # rows
    return h_pre, h_post, m


def read(X, h_pre):
    """``u (T, C) = Hpre X``."""
    return jnp.einsum("tn,tnc->tc", h_pre, X, precision=HI)


def write(X, h_res, h_post, y):
    """``X' = Hres X + Hpost^T y``."""
    return jnp.einsum("tij,tjc->tic", h_res, X, precision=HI) \
        + h_post[:, :, None] * y[:, None, :]


def _hc(w, sub):
    return {k: w[f"{sub}.{k}"] for k in ("phi", "alpha", "bias")}


# ------------------------------------------------------------- the layers
def _mla(w, a, pos, cfg, precision):
    """Expanded latent attention of one normed sequence ``a (T, U)`` with a
    dense causal mask: the sublayer's output ``(T, U)``."""
    T = a.shape[0]
    H, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kl, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq = cfg["inv_freq"]
    cq = _rms(_mm(a, _w(w["self_attn.q_a_proj"], precision)),
              w["self_attn.q_a_layernorm"], eps)
    q = _mm(cq, _w(w["self_attn.q_b_proj"], precision)).reshape(
        T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, inv_freq)
    ckv_kr = _mm(a, _w(w["self_attn.kv_a_proj_with_mqa"], precision))
    ckv = _rms(ckv_kr[:, :kl], w["self_attn.kv_a_layernorm"], eps)
    kr = _rope(ckv_kr[:, kl:], pos, inv_freq)
    kv = _mm(ckv, _w(w["self_attn.kv_b_proj"], precision)).reshape(
        T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HI)
         + jnp.einsum("qhr,kr->hqk", q_rope, kr, precision=HI)) \
        * cfg["softmax_scale"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(T, H * vd)
    return _mm(o, _w(w["self_attn.o_proj"], precision))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention_sublayer(w, X, pos, *, cfg_key, precision):
    cfg = dict(cfg_key)
    h_pre, h_post, h_res = coefficients(_hc(w, "hc_attn"), X, cfg, precision)
    a = _rms(read(X, h_pre), w["input_layernorm"], cfg["rms_norm_eps"])
    return write(X, h_res, h_post, _mla(w, a, pos, cfg, precision))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _dense_sublayer(w, X, *, cfg_key, precision):
    cfg = dict(cfg_key)
    h_pre, h_post, h_res = coefficients(_hc(w, "hc_ffn"), X, cfg, precision)
    m = _rms(read(X, h_pre), w["post_attention_layernorm"],
             cfg["rms_norm_eps"])
    y = _expert(m, _w(w["mlp.gate_proj"], precision),
                _w(w["mlp.up_proj"], precision),
                _w(w["mlp.down_proj"], precision))
    return write(X, h_res, h_post, y)


def route(scores, bias, cfg):
    """``noaux_tc`` with one group: the ``num_experts_per_tok`` largest of
    ``scores + bias``, ``weights = routed_scaling_factor * s_k / sum_chosen
    s`` of the scores WITHOUT the bias."""
    ids = jnp.argsort(-(scores + bias), axis=-1,
                      stable=True)[:, :cfg["num_experts_per_tok"]]
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, cfg["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _route_and_shared(w, X, *, cfg_key, precision):
    """Of one expert sublayer: the coefficients, the normed input ``m``, the
    choice ``(ids, weights)`` and the shared expert's output."""
    cfg = dict(cfg_key)
    coef = coefficients(_hc(w, "hc_ffn"), X, cfg, precision)
    m = _rms(read(X, coef[0]), w["post_attention_layernorm"],
             cfg["rms_norm_eps"])
    scores = jax.nn.sigmoid(_mm(m, _w(w["mlp.gate"], precision)))
    ids, wts = route(scores, w["mlp.gate.e_score_correction_bias"], cfg)
    y = _expert(m, _w(w["mlp.shared_experts.gate_proj"], precision),
                _w(w["mlp.shared_experts.up_proj"], precision),
                _w(w["mlp.shared_experts.down_proj"], precision))
    return coef, m, ids, wts, y


@jax.jit
def _write(X, h_res, h_post, y):
    return write(X, h_res, h_post, y)


def expert_rows(n_rows, n_tokens):
    """The padded count of an expert's rows: 64, 256, 1024 or the whole
    sequence, so that the shapes compiled are few."""
    return min(next((b for b in (64, 256, 1024) if n_rows <= b), n_tokens),
               n_tokens)


def _moe_sublayer(w, X, *, cfg_key, precision):
    """The expert sublayer: the held experts' part of the routed sum (a plain
    loop, each held expert applied to the tokens that chose it; a padded row
    adds zero) plus the shared expert, written back to the streams."""
    import numpy as np
    (_h_pre, h_post, h_res), m, ids, wts, y = _route_and_shared(
        w, X, cfg_key=cfg_key, precision=precision)
    chose = np.asarray(ids)
    for j, e in enumerate(dict(cfg_key)["held_experts"]):
        rows = np.nonzero((chose == e).any(-1))[0]
        if not rows.size:
            continue
        n = expert_rows(rows.size, chose.shape[0])
        padded = np.zeros((n,), "int32")
        padded[:rows.size] = rows
        y = _add_expert(
            y, m, ids, wts, jnp.asarray(padded),
            jnp.asarray(np.arange(n) < rows.size, jnp.float32),
            jnp.int32(e), w["mlp.experts.gate_proj"][j],
            w["mlp.experts.up_proj"][j], w["mlp.experts.down_proj"][j],
            precision=precision)
    return _write(X, h_res, h_post, y)


def _freeze(cfg):
    """The configuration's numbers as a hashable static argument."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps",
            "num_experts_per_tok", "routed_scaling_factor", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max")
    out = {k: cfg[k] for k in keys}
    out["held_experts"] = tuple(cfg["held_experts"])
    out["inv_freq"] = tuple(yarn_inv_freq(cfg))
    out["softmax_scale"] = softmax_scale(cfg)
    return tuple(sorted(out.items()))


def forward(w, cfg, tokens, precision="float32"):
    """Logits ``(T, vocab)`` of one sequence ``tokens (T,)``: row ``t``
    scores the token that follows position ``t``.  A sublayer at a time."""
    key, eps = _freeze(cfg), cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = w["embed_tokens"][tokens].astype(jnp.float32)
        X = jnp.repeat(h[:, None, :], cfg["hc_mult"], axis=1)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        for i in range(cfg["n_layer"]):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            X = _attention_sublayer(lw, X, pos, cfg_key=key,
                                    precision=precision)
            if i < cfg["first_k_dense_replace"]:
                X = _dense_sublayer(lw, X, cfg_key=key, precision=precision)
            else:
                X = _moe_sublayer(lw, X, cfg_key=key, precision=precision)
        return _head(w["norm"], w["lm_head"], X.sum(1), eps=eps,
                     precision=precision)


def gaps_by_precision(w, cfg, prompts, served, pad_to,
                      precisions=("float32",)):
    """``axk1.served_token_gaps`` under this forward pass, for several
    ``precisions`` at once (the float32 forward of a sequence is computed
    once): for finished requests, at each served position, how far the
    reference's logit of the served token lies below the reference's best;
    for a control, the token read is the one the control puts first there
    (teacher-forced on the same prompt and served tokens).  One sequence at
    a time, padded to the next multiple of ``pad_to``'s quarter (causal, so
    padding touches nothing before it).  ``{precision: float32 array of
    gaps over all served tokens, request after request}``."""
    import numpy as np
    step = max(pad_to // 4, 1)
    out = {p: [] for p in precisions}
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        n = -(-len(seq) // step) * step
        padded = np.zeros((n,), "int32")
        padded[:len(seq)] = seq
        padded = jnp.asarray(padded)
        # row t scores the token that follows position t
        lo, hi = len(prompt) - 1, len(seq) - 1
        ref = forward(w, cfg, padded)
        for p in precisions:
            if p == "float32":
                chosen = np.zeros((n,), "int32")
                chosen[lo:hi] = tokens
            else:
                chosen = jnp.argmax(forward(w, cfg, padded, p), axis=-1)
            out[p].append(np.asarray(_gaps(ref, jnp.asarray(chosen)),
                                     "float32")[lo:hi])
    return {p: np.concatenate(g) if g else np.zeros((0,), "float32")
            for p, g in out.items()}


def served_token_gaps(w, cfg, prompts, served, pad_to, precision="float32"):
    """:func:`gaps_by_precision` for one ``precision``."""
    return gaps_by_precision(w, cfg, prompts, served, pad_to,
                             (precision,))[precision]
