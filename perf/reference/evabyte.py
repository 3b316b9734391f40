"""Plain reference of EvaByte's language model (``model_type: evabyte``,
``attention_class: eva``, as ``huggingface.co/EvaByte/EvaByte`` configures
it; "Efficient Attention via Control Variates", arXiv:2302.04542, made
deterministic).  ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no ring, no page, no cache, no
batching, no kernel, and nothing imported from ``mxnet_tpu``.

With ``H`` heads of ``d`` dimensions, ``W = window_size``, ``c =
chunk_size``, ``s = d ** -0.5`` and, a layer and head, the learned ``phi,
mu`` (``adaptive_phi``, ``adaptive_mu_k``); the residual is float32:

    a     = RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * (1 + g)
    q,k,v = rope(a Wq), rope(a Wk), a Wv     rotate-half over all d, per head
    chunk j = positions c j .. c j + c - 1:
      kbar_j = sum_m softmax_m(s k_m . phi) k_m + mu
      vbar_j = sum_m softmax_m(s k_m . mu)  v_m
    query t, w = t // W:
      columns = {k_m : w W <= m <= t}  U  {kbar_j : j < w W / c}
      p = softmax over all columns of s q_t . column          ONE softmax
      o_t = sum p_m v_m + sum p_j vbar_j
    x <- x + o Wo;   x <- x + (silu(a' Wg) * (a' Wu)) Wd,  a' = RMSNorm'(x)
    logits = RMSNorm_f(x) Whead reshaped (num_pred_heads, vocab); head 0 is
    the next byte

It is DENSE masked attention: every query scores every key of the sequence
and every chunk's summary, ``[S keys | S / c summaries]`` side by side in one
softmax, and the mask decides: the definition.  Queries go a block of rows at
a time so that the scores of an 11,008-byte sequence fit beside the weights;
each query still sees every column.

The weights are the reference's own, made from the seed one tensor at a time
by the published names (``shapes`` is the table the system file uses too) and
kept as the configuration stores them; a layer is widened to float32 when it
is used.  What the published config does not fix is under ``assumed`` in
``perf/configs/evabyte_pp2.json``.

``precision`` selects a lower precision or a broken mechanism, each put in
the program's place by ``served_token_gaps``: ``"weights_fp8"`` rounds every
matrix through e4m3 with one scale a tensor; ``"summaries_off"`` drops the
summary columns (the far context lost: what a step that forgot its pages
would serve); ``"pool_uniform"`` makes both poolings plain means and adds no
``mu`` (what a program that ignored the two learned vectors would serve).
"""
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def shapes(cfg):
    """The tensors of the share, by the published module names: ``{name:
    (shape, kind, dtype)}``.  Matrices are stored ``(in, out)`` and applied as
    ``x W``.  ``kind``: ``normal`` (N(0, initializer_range), rounded to
    bfloat16), ``offset`` (a norm's ``g``, N(0, draws.norm_offset_std)),
    ``phi`` / ``mu`` (N(0, draws.phi_std) / N(0, draws.mu_std), a head)."""
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    f, wt = cfg["intermediate_size"], cfg["precision"]["weights"]
    d = u // H
    s = {"model.embed_tokens": ((cfg["vocab_size"], u), "normal", wt),
         "lm_head": ((u, cfg["num_pred_heads"] * cfg["vocab_size"]),
                     "normal", wt),
         "model.norm": ((u,), "offset", "float32")}
    for i in range(cfg["n_layer"]):
        p = f"model.layers.{i}."
        s.update({
            p + "input_layernorm": ((u,), "offset", "float32"),
            p + "self_attn.q_proj": ((u, u), "normal", wt),
            p + "self_attn.k_proj": ((u, u), "normal", wt),
            p + "self_attn.v_proj": ((u, u), "normal", wt),
            p + "self_attn.o_proj": ((u, u), "normal", wt),
            p + "self_attn.adaptive_phi": ((H, d), "phi", "float32"),
            p + "self_attn.adaptive_mu_k": ((H, d), "mu", "float32"),
            p + "post_attention_layernorm": ((u,), "offset", "float32"),
            p + "mlp.gate_proj": ((u, f), "normal", wt),
            p + "mlp.up_proj": ((u, f), "normal", wt),
            p + "mlp.down_proj": ((f, u), "normal", wt)})
    return s


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype", "std"))
def _draw(key, shape, kind, dtype, std):
    x = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "normal":
        x = x.astype(jnp.bfloat16)
    return x.astype(dtype)


def weights(cfg, seed_key, device=None):
    """``{name: array}`` of :func:`shapes` from ``seed_key`` (a PRNG key of
    the run's seed): one tensor at a time, tensor ``i`` of the sorted names
    from ``fold_in(seed_key, i)``."""
    if device is not None:
        seed_key = jax.device_put(seed_key, device)
    draws = cfg["draws"]
    std = {"normal": float(cfg["initializer_range"]),
           "offset": float(draws["norm_offset_std"]),
           "phi": float(draws["phi_std"]), "mu": float(draws["mu_std"])}
    return {name: _draw(jax.random.fold_in(seed_key, i), shape, kind, dtype,
                        std[kind])
            for i, (name, (shape, kind, dtype)) in enumerate(sorted(
                shapes(cfg).items()))}


# ------------------------------------------------------------- the layers
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


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


def _rope(x, pos, base):
    """``x (T, heads, d)`` rotated at ``pos (T,)``: dimension ``j`` pairs
    with ``j + d / 2`` (rotate-half), frequencies ``base ** (-2 j / d)``."""
    d = x.shape[-1]
    half = d // 2
    inv = jnp.asarray([base ** (-2.0 * j / d) for j in range(half)],
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, phi, mu, c, uniform=False):
    """``(kbar, vbar) (T / c, H, d)`` of ``k``, ``v (T, H, d)``: each chunk's
    keys pooled by ``softmax(s k . phi)`` with ``mu`` added, its values by
    ``softmax(s k . mu)``; ``uniform``: plain means and no ``mu``."""
    T, H, d = k.shape
    kc, vc = k.reshape(T // c, c, H, d), v.reshape(T // c, c, H, d)
    if uniform:
        return kc.mean(1), vc.mean(1)
    s = d ** -0.5
    wk = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, phi, precision=HI) * s,
                        axis=1)
    wv = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, mu, precision=HI) * s,
                        axis=1)
    return ((wk[..., None] * kc).sum(1) + mu, (wv[..., None] * vc).sum(1))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _attention(w, h, *, cfg_key, precision):
    """``h + EVA(RMSNorm(h)) Wo`` for one sequence ``h (T, U)``: dense, every
    query over every key and every chunk's summary in one softmax, the mask
    deciding."""
    z = dict(cfg_key)
    T = h.shape[0]
    H, W, c = z["heads"], z["window"], z["chunk"]
    d = h.shape[1] // H
    pos = jnp.arange(T, dtype=jnp.int32)
    a = _rms(h, w["input_layernorm"], z["eps"])
    q = _rope(_mm(a, _w(w["self_attn.q_proj"], precision)).reshape(T, H, d),
              pos, z["rope_theta"])
    k = _rope(_mm(a, _w(w["self_attn.k_proj"], precision)).reshape(T, H, d),
              pos, z["rope_theta"])
    v = _mm(a, _w(w["self_attn.v_proj"], precision)).reshape(T, H, d)
    kbar, vbar = summaries(k, v, w["self_attn.adaptive_phi"],
                           w["self_attn.adaptive_mu_k"], c,
                           uniform=precision == "pool_uniform")
    columns = jnp.concatenate([k, kbar], 0)          # [T keys | T / c chunks]
    values = jnp.concatenate([v, vbar], 0)
    chunk = jnp.arange(T // c)

    def block(q_pos):
        qb, pb = q_pos                               # (Q, H, d), (Q,)
        s = jnp.einsum("qhd,khd->hqk", qb, columns, precision=HI) \
            / math.sqrt(d)
        start = (pb // W * W)[:, None]
        exact = (pos[None, :] >= start) & (pos[None, :] <= pb[:, None])
        far = chunk[None, :] < start // c
        if precision == "summaries_off":
            far = jnp.zeros_like(far)
        ok = jnp.concatenate([exact, far], -1)
        pr = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, values, precision=HI)

    Q = min(T, z["query_block"])
    if T % Q or T % c:
        raise ValueError(f"a sequence of {T} positions is not whole blocks "
                         f"of {Q} queries and chunks of {c}")
    o = jax.lax.map(block, (q.reshape(T // Q, Q, H, d),
                            pos.reshape(T // Q, Q)))
    return h + _mm(o.reshape(T, H * d), _w(w["self_attn.o_proj"], precision))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _mlp(w, h, *, eps, precision):
    m = _rms(h, w["post_attention_layernorm"], eps)
    return h + _mm(jax.nn.silu(_mm(m, _w(w["mlp.gate_proj"], precision)))
                   * _mm(m, _w(w["mlp.up_proj"], precision)),
                   _w(w["mlp.down_proj"], precision))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(norm, head, h, *, eps, precision):
    return _mm(_rms(h, norm, eps), _w(head, precision))


def _freeze(cfg, query_block):
    """The configuration's numbers as a hashable static argument."""
    out = {"heads": cfg["num_attention_heads"], "window": cfg["window_size"],
           "chunk": cfg["chunk_size"], "eps": cfg["rms_norm_eps"],
           "rope_theta": float(cfg["rope_theta"]),
           "query_block": int(query_block)}
    return tuple(sorted(out.items()))


def forward(w, cfg, tokens, precision="float32", query_block=256):
    """Logits ``(T, num_pred_heads, vocab)`` of one sequence ``tokens (T,)``
    (``T`` whole chunks): row ``t`` of head ``i`` scores the byte ``i + 1``
    after position ``t``.  A layer at a time."""
    key, eps = _freeze(cfg, query_block), cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = w["model.embed_tokens"][tokens].astype(jnp.float32)
        for i in range(cfg["n_layer"]):
            p = f"model.layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            h = _attention(lw, h, cfg_key=key, precision=precision)
            h = _mlp(lw, h, eps=eps, precision=precision)
        logits = _head(w["model.norm"], w["lm_head"], h, eps=eps,
                       precision=precision)
    return logits.reshape(-1, cfg["num_pred_heads"], cfg["vocab_size"])


@jax.jit
def _gaps(ref, chosen):
    return ref.max(axis=-1) - jnp.take_along_axis(
        ref, chosen[:, None], axis=-1)[:, 0]


def served_token_gaps(w, cfg, prompts, served, pad_to, precision="float32"):
    """For finished requests: at each served position, how far the
    reference's head-0 logit of the served byte lies below the reference's
    best (0 where the served byte IS the reference's choice).

    With ``precision`` set to a lower one (or a broken mechanism), the byte
    read is not the served one but the byte that it puts first at that
    position (teacher-forced on the same prompt and served bytes): the
    control.  One sequence at a time, padded to the next multiple of a
    twelfth of ``pad_to`` (1,024 of 12,288; every layer is causal, so padding
    touches nothing before it).  Returns one float32 array of gaps over all
    served bytes, request after request."""
    import numpy as np
    step = max(pad_to // 12, cfg["chunk_size"])
    block = math.gcd(step, 256)
    out = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        n = -(-len(seq) // step) * step
        padded = np.zeros((n,), "int32")
        padded[:len(seq)] = seq
        chosen = np.zeros((n,), "int32")
        # row t scores the byte that follows position t
        lo, hi = len(prompt) - 1, len(seq) - 1
        chosen[lo:hi] = tokens
        ref = forward(w, cfg, jnp.asarray(padded), query_block=block)[:, 0]
        if precision != "float32":
            chosen = jnp.argmax(forward(w, cfg, jnp.asarray(padded), precision,
                                        query_block=block)[:, 0], axis=-1)
        out.append(np.asarray(_gaps(ref, jnp.asarray(chosen)),
                              "float32")[lo:hi])
    return np.concatenate(out) if out else np.zeros((0,), "float32")
