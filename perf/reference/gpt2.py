"""Plain reference of GPT-2's forward pass (Radford et al. 2019): pre-LN
blocks, learned positions, tanh GELU, tied output head.  ``jax.numpy``,
float32, matrix products at "highest" precision; no cache, no batching, no
kernel, and nothing imported from ``mxnet_tpu``.

``precision`` selects the lower-precision controls of the correctness check:
``"bfloat16"`` rounds every matrix product's inputs to bfloat16 (f32
accumulation), ``"kv_int8"`` stores each token's keys and values as 8-bit
codes with one affine scale per token, and ``"kv_fp8"`` as e4m3 with one
scale per token — the storage a quantized KV cache keeps.
"""
import functools

import jax
import jax.numpy as jnp


def shapes(cfg):
    """GPT-2's tensors (Radford et al. 2019; HF ``GPT2LMHeadModel``): tied
    embedding ``wte``, learned positions ``wpe``, per block ``ln_1``,
    ``attn.c_attn`` (u, 3u), ``attn.c_proj``, ``ln_2``, ``mlp.c_fc``,
    ``mlp.c_proj``, final ``ln_f``.  Matrices and embeddings are
    N(0, initializer_range), gains one, biases zero, as published."""
    u, hid = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    s = {"wte": ((cfg["vocab_size"], u), "normal"),
         "wpe": ((cfg["n_positions"], u), "normal"),
         "ln_f.g": ((u,), "ones"), "ln_f.b": ((u,), "zeros")}
    for i in range(cfg["n_layer"]):
        p = f"h{i}."
        s.update({
            p + "ln_1.g": ((u,), "ones"), p + "ln_1.b": ((u,), "zeros"),
            p + "attn.c_attn.w": ((u, 3 * u), "normal"),
            p + "attn.c_attn.b": ((3 * u,), "zeros"),
            p + "attn.c_proj.w": ((u, u), "normal"),
            p + "attn.c_proj.b": ((u,), "zeros"),
            p + "ln_2.g": ((u,), "ones"), p + "ln_2.b": ((u,), "zeros"),
            p + "mlp.c_fc.w": ((u, hid), "normal"),
            p + "mlp.c_fc.b": ((hid,), "zeros"),
            p + "mlp.c_proj.w": ((hid, u), "normal"),
            p + "mlp.c_proj.b": ((u,), "zeros")})
    return s


def _ln(x, g, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def _mm(a, b, precision):
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _store_kv(x, precision):
    """What a quantized cache hands back for a (T, H, D) tensor."""
    if precision == "kv_int8":
        lo = x.min(axis=(-2, -1), keepdims=True)
        hi = x.max(axis=(-2, -1), keepdims=True)
        scale = jnp.where(hi > lo, (hi - lo) / 254.0, 1.0)
        mid = (hi + lo) / 2.0
        return jnp.clip(jnp.round((x - mid) / scale), -127, 127) * scale + mid
    if precision == "kv_fp8":
        amax = jnp.abs(x).max(axis=(-2, -1), keepdims=True)
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x


@functools.partial(jax.jit, static_argnames=("n_layer", "n_head", "eps",
                                             "precision"))
def forward(w, tokens, *, n_layer, n_head, eps, precision="float32"):
    """Logits ``(T, vocab)`` of one sequence ``tokens (T,)``: row ``t`` scores
    the token that follows position ``t``."""
    T = tokens.shape[0]
    u = w["wte"].shape[1]
    d = u // n_head
    mm = functools.partial(_mm, precision="bfloat16"
                           if precision == "bfloat16" else "float32")
    h = w["wte"][tokens] + w["wpe"][:T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(n_layer):
        p = f"h{i}."
        a = _ln(h, w[p + "ln_1.g"], w[p + "ln_1.b"], eps)
        qkv = mm(a, w[p + "attn.c_attn.w"]) + w[p + "attn.c_attn.b"]
        q, k, v = (x.reshape(T, n_head, d) for x in jnp.split(qkv, 3, -1))
        k, v = _store_kv(k, precision), _store_kv(v, precision)
        s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / jnp.sqrt(
            jnp.float32(d))
        s = jnp.where(causal[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        ctx = mm(pr, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(T, u)
        h = h + mm(ctx, w[p + "attn.c_proj.w"]) + w[p + "attn.c_proj.b"]
        m = _ln(h, w[p + "ln_2.g"], w[p + "ln_2.b"], eps)
        f = _gelu_tanh(mm(m, w[p + "mlp.c_fc.w"]) + w[p + "mlp.c_fc.b"])
        h = h + mm(f, w[p + "mlp.c_proj.w"]) + w[p + "mlp.c_proj.b"]
    hf = _ln(h, w["ln_f.g"], w["ln_f.b"], eps)
    return mm(hf, w["wte"].T)


def served_token_gaps(w, cfg, prompts, served, pad_to, precision="float32",
                      chunk=8):
    """For finished requests: at each served position, how far the
    reference's logit of the served token lies below the reference's best
    (0 where the served token IS the reference's choice).

    With ``precision`` set to a lower one, the token read is not the served
    one but the token that the lower precision puts first at that position
    (teacher-forced on the same prompt and served tokens): the control.
    ``prompts`` and ``served`` are lists of token lists; sequences are
    padded to ``pad_to`` (causal, so padding touches nothing before it) and
    run ``chunk`` at a time.  Returns one float32 array of gaps over all
    served tokens, request after request."""
    import numpy as np
    kw = dict(n_layer=cfg["n_layer"], n_head=cfg["n_head"],
              eps=cfg["layer_norm_epsilon"])

    # the weights are an argument, not a closure: closed over, 1.4 GB of
    # them would be baked into the program as constants
    @functools.partial(jax.jit, static_argnames=("prec",))
    def gaps_of(w, tokens, chosen, prec):
        """tokens (c, pad_to); chosen (c, pad_to): the token whose logit is
        read at each row (ignored where a lower precision chooses)."""
        def many(p):
            return jax.vmap(lambda t: forward(w, t, precision=p, **kw))
        ref = many("float32")(tokens)
        if prec != "float32":
            chosen = jnp.argmax(many(prec)(tokens), axis=-1)
        return ref.max(axis=-1) - jnp.take_along_axis(
            ref, chosen[..., None], axis=-1)[..., 0]

    out = []
    for c0 in range(0, len(prompts), chunk):
        rows = list(range(c0, min(c0 + chunk, len(prompts))))
        tokens = np.zeros((chunk, pad_to), "int32")
        chosen = np.zeros((chunk, pad_to), "int32")
        for j, i in enumerate(rows):
            seq = list(prompts[i]) + list(served[i])
            tokens[j, :len(seq)] = seq
            # row t scores the token that follows position t
            chosen[j, len(prompts[i]) - 1:len(seq) - 1] = served[i]
        g = np.asarray(gaps_of(w, jnp.asarray(tokens), jnp.asarray(chosen),
                               prec=precision), "float32")
        for j, i in enumerate(rows):
            out.append(g[j, len(prompts[i]) - 1:
                         len(prompts[i]) + len(served[i]) - 1])
    return np.concatenate(out) if out else np.zeros((0,), "float32")
