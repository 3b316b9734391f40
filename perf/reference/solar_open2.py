"""Plain reference of Solar-Open2-250B's forward pass (the ``solar_open2``
family's layer as ``huggingface.co/upstage/Solar-Open2-250B`` configures
it): every layer is ``h <- h + mixer(RMSNorm(h))``, ``h <- h + experts(
RMSNorm(h))``, then a final norm and an untied head.  ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``; no cache, no
chunks, no kernel, a plain loop over the held experts, and nothing imported
from ``mxnet_tpu`` (the helpers every routed-expert reference shares come
from ``perf/reference/mimo_v2.py``).

The mixer is grouped-query attention at the layers ``gqa_layers`` names
(0, 4, 8, ...) and KDA, the gated delta rule with a decay a key channel,
at the three layers between.

**The KDA layer is the sequential recurrence**, a ``lax.scan`` over the
tokens of the sequence, the definition (64 heads, ``dk = dv = 128``):

    q = silu(conv4(u W_q)), k = silu(conv4(u W_k)), v = silu(conv4(u W_v))
        depthwise, causal, 4 taps, zeros before the sequence, no bias
    q_h <- q_h / |q_h| * 128^-0.5;  k_h <- k_h / |k_h|      (eps 1e-6)
    g = -exp(A_log_h) softplus((u W_f1) W_f2 + dt_bias)      a key CHANNEL
    beta_h = 2 sigmoid(u W_b)                                in (0, 2)
    S_h <- diag(exp(g)) S_h;  w = beta_h (v_h - S_h^T k_h)
    S_h <- S_h + k_h w^T;     o_h = S_h^T q_h
    y = RMSNorm_128(o_h) * gain * sigmoid((u W_g1) W_g2 + b_g);  out = y W_o

Grouped-query attention: ``q`` 64 heads x 128, ``k``, ``v`` 8 heads x 128,
causal softmax of ``q k^T / sqrt(128)``, query head ``j`` reads K/V head ``j
// 8``, no bias and **no rotary** (``use_rope: false``), the heads' outputs
times ``sigmoid(u W_gate)`` elementwise (``use_gqa_gate``), then ``W_o``.
It is DENSE masked attention: every query scores every key of the sequence
and the mask decides.  Queries go a block at a time so that the ``(heads,
queries, keys)`` scores of a 5,120-token sequence fit beside the weights;
each query still sees all keys.

Experts, every layer: ``s = sigmoid(u W_r)`` over all published experts,
the 8 largest, ``w_k = routed_scaling_factor * s_k / sum_chosen s``, ``sum_k
w_k E_k(u)`` over the chosen experts that are HELD, ``E(u) = (silu(u W_g) *
(u W_u)) W_d`` at width 1280, plus one shared expert of the same form,
unweighted.

It is given the same share of the deployment as the program
(``cfg["held_experts"]``, the sliced vocabulary, the first ``n_layer``
layers): what the absent experts would have added is left out, here as in
the program.

The weights are the reference's own, made from the seed one tensor at a time
(``shapes`` is the table the system file uses too) and kept as the
configuration stores them; a layer is widened to float32 when it is used.
Departures from the published model are under ``assumed`` in
``perf/configs/solar_open2_ep16.json``: what the config does not give (the
gates' rank, the draws of ``A_log``, ``dt_bias`` and the taps, the norms
and activations the family uses) is the family's convention, and the
program's rounding of the convolutions' input to bfloat16 is not made here.

``precision`` selects a lower precision or a broken mechanism, each put in
the program's place by ``served_token_gaps``: ``"weights_fp8"`` rounds
every matrix through e4m3 with one scale a tensor; ``"decay_off"`` sets
``g = 0`` (a state that never forgets: the plain delta rule);
``"neg_eig_off"`` drops the factor 2 of ``beta`` (``kda_allow_neg_eigval``
false).
"""
import functools
import math

import jax
import jax.numpy as jnp

# what every plain reference of a routed-expert family needs, said once:
# the RMS norm, a stored matrix widened (through e4m3 for ``weights_fp8``),
# the product at the highest precision, a SwiGLU expert and its addition to
# the tokens that chose it, the head, a served token's gap
from .mimo_v2 import (HI, _add_expert, _expert, _gaps, _head, _mm, _rms,  # noqa: F401
                      _w)


def gqa_layers(cfg):
    """The depths of the grouped-query layers this chip runs."""
    return tuple(i for i in cfg["gqa_layers"] if i < cfg["n_layer"])


def sizes(cfg):
    """The widths the layers are built from."""
    lin = cfg["linear_attn_config"]
    H = lin["num_heads"]
    return {"u": cfg["hidden_size"], "H": H, "dk": lin["head_dim"],
            "kw": H * lin["head_dim"], "K": lin["short_conv_kernel_size"],
            "rank": cfg["kda_gate_rank"],
            "q_heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["moe_intermediate_size"]}


def shapes(cfg):
    """The tensors of the share, by the family's module names: ``{name:
    (shape, kind, dtype)}``.  Matrices are stored ``(in, out)`` and applied
    as ``x W``.  ``kind``: ``normal`` (N(0, initializer_range), rounded to
    bfloat16), ``ones``, ``zeros``, ``conv`` (uniform in +-1/sqrt(taps)),
    ``dt_bias`` (the inverse softplus of a step log-uniform in
    [time_step_min, time_step_max], floored), ``a_log`` (log of a uniform
    in [1, 16]).  Experts are stacked ``(held, in, out)`` in the order of
    ``cfg["held_experts"]``."""
    z = sizes(cfg)
    u, kw, r, f = z["u"], z["kw"], z["rank"], z["f"]
    wt = cfg["precision"]["weights"]
    G, E = len(cfg["held_experts"]), cfg["published"]["n_routed_experts"]
    qw, kvw = z["q_heads"] * z["hd"], z["kv_heads"] * z["hd"]
    s = {"embed_tokens": ((cfg["vocab_size"], u), "normal", wt),
         "lm_head": ((u, cfg["vocab_size"]), "normal", wt),
         "norm": ((u,), "ones", "float32")}
    for i in range(cfg["n_layer"]):
        p = f"layers.{i}."
        s[p + "input_layernorm"] = ((u,), "ones", "float32")
        a = p + "self_attn."
        if i in gqa_layers(cfg):
            s.update({a + "q_proj": ((u, qw), "normal", wt),
                      a + "k_proj": ((u, kvw), "normal", wt),
                      a + "v_proj": ((u, kvw), "normal", wt),
                      a + "g_proj": ((u, qw), "normal", wt),
                      a + "o_proj": ((qw, u), "normal", wt)})
        else:
            for x in "qkv":
                s[a + x + "_proj"] = ((u, kw), "normal", wt)
                s[a + x + "_conv1d"] = ((kw, z["K"]), "conv", "float32")
            s.update({a + "f_a_proj": ((u, r), "normal", wt),
                      a + "f_b_proj": ((r, kw), "normal", wt),
                      a + "A_log": ((z["H"],), "a_log", "float32"),
                      a + "dt_bias": ((kw,), "dt_bias", "float32"),
                      a + "b_proj": ((u, z["H"]), "normal", wt),
                      a + "g_a_proj": ((u, r), "normal", wt),
                      a + "g_b_proj": ((r, kw), "normal", wt),
                      a + "g_b_proj.bias": ((kw,), "zeros", "float32"),
                      a + "o_norm": ((z["dk"],), "ones", "float32"),
                      a + "o_proj": ((kw, u), "normal", wt)})
        s.update({
            p + "post_attention_layernorm": ((u,), "ones", "float32"),
            # the router's matrix is float32 (its scores are computed in
            # float32); its values are bfloat16-rounded
            p + "mlp.gate": ((u, E), "normal", "float32"),
            p + "mlp.experts.gate_proj": ((G, u, f), "normal", wt),
            p + "mlp.experts.up_proj": ((G, u, f), "normal", wt),
            p + "mlp.experts.down_proj": ((G, f, u), "normal", wt),
            p + "mlp.shared_experts.gate_proj": ((u, f), "normal", wt),
            p + "mlp.shared_experts.up_proj": ((u, f), "normal", wt),
            p + "mlp.shared_experts.down_proj": ((f, u), "normal", wt)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype",
                                              "numbers"))
def _draw(key, shape, kind, dtype, numbers):
    std, k, lo, hi, floor = numbers
    if kind == "normal":
        x = (jax.random.normal(key, shape, jnp.float32) * std).astype(
            jnp.bfloat16)
    elif kind == "conv":
        bound = 1.0 / math.sqrt(k)
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        x = dt + jnp.log(-jnp.expm1(-dt))       # softplus(x) == dt
    else:
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    return x.astype(dtype)


def weights(cfg, seed_key, device=None):
    """``{name: array}`` of :func:`shapes` from ``seed_key`` (a PRNG key of
    the run's seed): one tensor at a time, tensor ``i`` of the sorted names
    from ``fold_in(seed_key, i)``."""
    if device is not None:
        seed_key = jax.device_put(seed_key, device)
    numbers = (float(cfg["initializer_range"]),
               int(cfg["linear_attn_config"]["short_conv_kernel_size"]),
               float(cfg["time_step_min"]), float(cfg["time_step_max"]),
               float(cfg["time_step_floor"]))
    out = {}
    for i, (name, (shape, kind, dtype)) in enumerate(sorted(
            shapes(cfg).items())):
        if kind in ("ones", "zeros"):
            out[name] = jax.device_put(
                getattr(jnp, kind)(shape, dtype), device)
        else:
            out[name] = _draw(jax.random.fold_in(seed_key, i), shape, kind,
                              dtype, numbers)
    return out


# ------------------------------------------------------------- the layers
def _conv(x, taps):
    """``silu`` of the depthwise causal convolution of ``x (T, C)`` with
    ``taps (C, K)``: ``out_t = sum_j taps[:, j] x_{t-(K-1)+j}``, zeros
    before the sequence."""
    T, K = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + T] * taps[:, j] for j in range(K)))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _recurrence(q, k, v, g, beta):
    """One sequence: ``q``, ``k``, ``g (T, H, dk)``, ``v (T, H, dv)``,
    ``beta (T, H)``.  The definition, a token at a time."""
    def one(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        w = b_t[:, None] * (v_t - (S * k_t[:, :, None]).sum(1))
        S = S + k_t[:, :, None] * w[:, None, :]
        return S, (S * q_t[:, :, None]).sum(1)

    S0 = jnp.zeros(k.shape[1:] + (v.shape[-1],), jnp.float32)
    return jax.lax.scan(one, S0, (q, k, v, g, beta))[1]


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _kda(w, h, *, cfg_key, precision):
    """``h + KDA(RMSNorm(h))`` for one sequence ``h (T, U)``."""
    z = dict(cfg_key)
    T, H, dk = h.shape[0], z["H"], z["dk"]
    W = lambda name: _w(w["self_attn." + name], precision)
    a = _rms(h, w["input_layernorm"], z["eps"])
    q, k, v = (_conv(_mm(a, W(x + "_proj")), w["self_attn." + x + "_conv1d"]
                     ).reshape(T, H, dk) for x in "qkv")
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    dt = jax.nn.softplus(_mm(_mm(a, W("f_a_proj")), W("f_b_proj"))
                         + w["self_attn.dt_bias"]).reshape(T, H, dk)
    g = -jnp.exp(w["self_attn.A_log"])[:, None] * dt
    if precision == "decay_off":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm(a, W("b_proj")))
    if precision != "neg_eig_off":
        beta = 2.0 * beta
    o = _recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid(_mm(_mm(a, W("g_a_proj")), W("g_b_proj"))
                          + w["self_attn.g_b_proj.bias"])
    y = _rms(o, w["self_attn.o_norm"], z["eps"]).reshape(T, H * dk) * gate
    return h + _mm(y, W("o_proj"))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(w, h, *, cfg_key, precision):
    """``h + GQA(RMSNorm(h))`` for one sequence ``h (T, U)``: dense, every
    query over every key of its K/V head, the mask deciding; no rotary; the
    output gated elementwise."""
    z = dict(cfg_key)
    T, Hq, Hkv, hd = h.shape[0], z["q_heads"], z["kv_heads"], z["hd"]
    W = lambda name: _w(w["self_attn." + name], precision)
    pos = jnp.arange(T, dtype=jnp.int32)
    a = _rms(h, w["input_layernorm"], z["eps"])
    q = _mm(a, W("q_proj")).reshape(T, Hq, hd)
    k = jnp.repeat(_mm(a, W("k_proj")).reshape(T, Hkv, hd), Hq // Hkv,
                   axis=1)                      # query head j: KV head j // r
    v = jnp.repeat(_mm(a, W("v_proj")).reshape(T, Hkv, hd), Hq // Hkv,
                   axis=1)

    def block(q_pos):
        qb, pb = q_pos                          # (Q, H, hd), (Q,)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        s = jnp.where((pos[None, :] <= pb[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    Q = min(T, z["query_block"])
    if T % Q:
        raise ValueError(f"a sequence of {T} positions is not whole blocks "
                         f"of {Q} queries")
    o = jax.lax.map(block, (q.reshape(T // Q, Q, Hq, hd),
                            pos.reshape(T // Q, Q)))
    y = o.reshape(T, Hq * hd) * jax.nn.sigmoid(_mm(a, W("g_proj")))
    return h + _mm(y, W("o_proj"))


def route(scores, k, scale):
    """``(ids (T, k), weights (T, k))`` over ``scores (T, E)``: the ``k``
    largest (no group, no selection bias), ``weights = scale * s_k /
    sum_chosen s``."""
    ids = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, scale * chosen / chosen.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _route_and_shared(w, h, *, cfg_key, precision):
    """``(m, ids, weights, h + E_shared(m))`` of one expert sublayer over
    tokens ``h (T, U)``."""
    z = dict(cfg_key)
    W = lambda name: _w(w["mlp." + name], precision)
    m = _rms(h, w["post_attention_layernorm"], z["eps"])
    ids, wts = route(jax.nn.sigmoid(_mm(m, W("gate"))), z["top_k"],
                     z["routed_scale"])
    y = _expert(m, W("shared_experts.gate_proj"),
                W("shared_experts.up_proj"), W("shared_experts.down_proj"))
    return m, ids, wts, h + y


def _experts(w, h, *, cfg_key, precision):
    """``h + sum over the chosen experts held here of w_k E_k(m) +
    E_shared(m)``: a plain loop over the held experts, each applied to the
    tokens that chose it (their count padded to 64, 256, 1,024, ... so that
    the shapes, each of which is compiled, are few; a padded row adds
    zero)."""
    import numpy as np
    m, ids, wts, y = _route_and_shared(w, h, cfg_key=cfg_key,
                                       precision=precision)
    chose = np.asarray(ids)
    for j, e in enumerate(dict(cfg_key)["held_experts"]):
        rows = np.nonzero((chose == e).any(-1))[0]
        if not rows.size:
            continue
        n = 64
        while n < rows.size:
            n *= 4
        n = min(n, chose.shape[0])
        padded = np.zeros((n,), "int32")
        padded[:rows.size] = rows
        y = _add_expert(
            y, m, ids, wts, jnp.asarray(padded),
            jnp.asarray(np.arange(n) < rows.size, jnp.float32),
            jnp.int32(e), w["mlp.experts.gate_proj"][j],
            w["mlp.experts.up_proj"][j], w["mlp.experts.down_proj"][j],
            precision=precision)
    return y


def _freeze(cfg, query_block):
    """The configuration's numbers as a hashable static argument."""
    out = dict(sizes(cfg), eps=cfg["rms_norm_eps"],
               top_k=cfg["num_experts_per_tok"],
               routed_scale=float(cfg["routed_scaling_factor"]),
               held_experts=tuple(cfg["held_experts"]),
               query_block=int(query_block))
    return tuple(sorted(out.items()))


def forward(w, cfg, tokens, precision="float32", query_block=512):
    """Logits ``(T, vocab)`` of one sequence ``tokens (T,)``: row ``t``
    scores the token that follows position ``t``.  A layer at a time."""
    key, eps = _freeze(cfg, query_block), cfg["rms_norm_eps"]
    gqa = gqa_layers(cfg)
    with jax.default_matmul_precision("highest"):
        h = w["embed_tokens"][tokens].astype(jnp.float32)
        for i in range(cfg["n_layer"]):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            mixer = _attention if i in gqa else _kda
            h = mixer(lw, h, cfg_key=key, precision=precision)
            h = _experts(lw, h, cfg_key=key, precision=precision)
        return _head(w["norm"], w["lm_head"], h, eps=eps,
                     precision=precision)


def served_token_gaps(w, cfg, prompts, served, pad_to, precision="float32"):
    """For finished requests: at each served position, how far the
    reference's logit of the served token lies below the reference's best
    (0 where the served token IS the reference's choice).

    With ``precision`` set to a lower one (or a broken mechanism), the token
    read is not the served one but the token that it puts first at that
    position (teacher-forced on the same prompt and served tokens): the
    control.  One sequence at a time, padded to the next multiple of
    ``pad_to``'s fifth (1,024 of 5,120: five lengths to compile; every
    layer is causal, so padding touches nothing before it).  Returns one
    float32 array of gaps over all served tokens, request after request."""
    import numpy as np
    step = max(pad_to // 5, 1)
    block = min(step, 512)          # queries at a time: divides every length
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
        ref = forward(w, cfg, jnp.asarray(padded), query_block=block)
        if precision != "float32":
            chosen = jnp.argmax(forward(w, cfg, jnp.asarray(padded),
                                        precision, query_block=block), axis=-1)
        out.append(np.asarray(_gaps(ref, jnp.asarray(chosen)),
                              "float32")[lo:hi])
    return np.concatenate(out) if out else np.zeros((0,), "float32")
