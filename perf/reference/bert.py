"""Plain reference of BERT pre-training (Devlin et al. 2019): post-LN
encoder, erf GELU, masked-LM head tied to the word embedding plus the
next-sentence head, summed mean cross-entropies, and Adam as MXNet 1.5
states it (learning rate carries the bias correction; epsilon outside the
root).  ``jax.numpy``, float32, matrix products at "highest" precision,
nothing imported from ``mxnet_tpu``.

It follows the program's first steps on the same rows, in blocks of rows so
that it fits beside nothing and stays under the program's own memory peak.

Dropout.  A mask is not mathematics the paper fixes, so the benchmark fixes
the convention and hands the program its step keys (``mx.random.set_state``):
inside one step, every dropout call, in forward order (embedding; then per
layer attention probabilities, attention output, feed-forward output), takes
``key, sub = jax.random.split(key)`` and keeps an element where
``jax.random.bernoulli(sub, 1 - rate, shape)`` is true, scaling by
``1 / (1 - rate)``.  Shapes are those of the whole batch: (B, T, C), and
(B * heads, T, T) for the probabilities.

``precision`` selects the lower-precision controls: ``"bfloat16"`` rounds
matrix-product inputs to bfloat16, ``"fp8"`` to float8_e4m3fn after scaling
each tensor to the format's range (f32 accumulation in both).
"""
import functools

import jax
import jax.numpy as jnp


def shapes(cfg):
    """BERT's tensors (Devlin et al. 2019; HF ``BertForPreTraining``) with
    Dense weights stored (out, in).  Embeddings and matrices are
    N(0, initializer_range), gains one, biases zero, as published."""
    u, hid = cfg["hidden_size"], cfg["intermediate_size"]
    s = {"word_embed": ((cfg["vocab_size"], u), "normal"),
         "pos_embed": ((cfg["max_position_embeddings"], u), "normal"),
         "type_embed": ((cfg["type_vocab_size"], u), "normal"),
         "embed_ln.g": ((u,), "ones"), "embed_ln.b": ((u,), "zeros"),
         "pooler.w": ((u, u), "normal"), "pooler.b": ((u,), "zeros"),
         "mlm_transform.w": ((u, u), "normal"),
         "mlm_transform.b": ((u,), "zeros"),
         "mlm_ln.g": ((u,), "ones"), "mlm_ln.b": ((u,), "zeros"),
         "mlm_bias": ((cfg["vocab_size"],), "zeros"),
         "nsp.w": ((2, u), "normal"), "nsp.b": ((2,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        s.update({
            p + "qkv.w": ((3 * u, u), "normal"),
            p + "qkv.b": ((3 * u,), "zeros"),
            p + "attn_out.w": ((u, u), "normal"),
            p + "attn_out.b": ((u,), "zeros"),
            p + "attn_ln.g": ((u,), "ones"), p + "attn_ln.b": ((u,), "zeros"),
            p + "ffn1.w": ((hid, u), "normal"), p + "ffn1.b": ((hid,), "zeros"),
            p + "ffn2.w": ((u, hid), "normal"), p + "ffn2.b": ((u,), "zeros"),
            p + "ffn_ln.g": ((u,), "ones"), p + "ffn_ln.b": ((u,), "zeros")})
    return s


def _mm(a, b, precision):
    """a (..., k) @ b (k, n)."""
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        def q(x):
            # round to the e4m3 grid after scaling the tensor to the
            # format's range; the gradient passes straight through, as
            # fp8 training keeps it (a cotangent cast to e4m3 is zero)
            s = jax.lax.stop_gradient(
                jnp.maximum(jnp.abs(x).max(), 1e-30) / 448.0)
            y = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return x + jax.lax.stop_gradient(y - x)
        return jnp.matmul(q(a), q(b), precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _ln(x, g, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


def _dense(x, w, b, precision):
    return _mm(x, w.T, precision) + b


class _Dropout:
    """The key chain of the module docstring, sliced to a block of rows."""

    def __init__(self, key, batch, r0, rows):
        self.key, self.batch, self.r0, self.rows = key, batch, r0, rows

    def __call__(self, x, rate, per_row=1):
        """``x`` holds ``rows * per_row`` leading entries of a whole-batch
        tensor whose leading axis is ``batch * per_row``.  A rate of zero
        draws no key, as a layer that is not there draws none."""
        if not rate:
            return x
        self.key, sub = jax.random.split(self.key)
        keep = 1.0 - rate
        full = (self.batch * per_row,) + x.shape[1:]
        mask = jax.random.bernoulli(sub, keep, full)
        mask = jax.lax.dynamic_slice_in_dim(mask, self.r0 * per_row,
                                            self.rows * per_row, axis=0)
        return x * mask.astype(x.dtype) / keep


def block_loss(w, batch, key, r0, *, cfg, batch_rows, rows, precision):
    """The part of the step's loss that rows ``r0 .. r0 + rows`` contribute:
    summing it over the blocks gives the mean masked-LM cross-entropy plus
    the mean next-sentence cross-entropy of the whole batch."""
    tokens, segments, valid, positions, mlm_labels, nsp_labels = (
        jax.lax.dynamic_slice_in_dim(a, r0, rows, axis=0) for a in batch)
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    T = tokens.shape[1]
    u = w["word_embed"].shape[1]
    d = u // H
    drop = _Dropout(key, batch_rows, r0, rows)
    rate_h = cfg["hidden_dropout_prob"]
    rate_a = cfg["attention_probs_dropout_prob"]
    mm = functools.partial(_mm, precision=precision)
    dense = functools.partial(_dense, precision=precision)

    x = w["word_embed"][tokens] + w["pos_embed"][:T][None] \
        + w["type_embed"][segments]
    x = drop(_ln(x, w["embed_ln.g"], w["embed_ln.b"], eps), rate_h)
    neg = (1.0 - valid)[:, None, None, :] * -1e30
    for i in range(L):
        p = f"l{i}."
        qkv = dense(x, w[p + "qkv.w"], w[p + "qkv.b"])
        q, k, v = (a.reshape(rows, T, H, d).transpose(0, 2, 1, 3)
                   for a in jnp.split(qkv, 3, axis=-1))
        s = mm(q / jnp.sqrt(jnp.float32(d)), k.transpose(0, 1, 3, 2)) + neg
        pr = jax.nn.softmax(s, axis=-1).reshape(rows * H, T, T)
        pr = drop(pr, rate_a, per_row=H).reshape(rows, H, T, T)
        ctx = mm(pr, v).transpose(0, 2, 1, 3).reshape(rows, T, u)
        out = dense(ctx, w[p + "attn_out.w"], w[p + "attn_out.b"])
        x = _ln(drop(out, rate_h) + x, w[p + "attn_ln.g"], w[p + "attn_ln.b"], eps)
        f = dense(_gelu(dense(x, w[p + "ffn1.w"], w[p + "ffn1.b"])),
                  w[p + "ffn2.w"], w[p + "ffn2.b"])
        x = _ln(drop(f, rate_h) + x, w[p + "ffn_ln.g"], w[p + "ffn_ln.b"], eps)
    pooled = jnp.tanh(dense(x[:, 0], w["pooler.w"], w["pooler.b"]))
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    h = _ln(_gelu(dense(picked, w["mlm_transform.w"], w["mlm_transform.b"])),
            w["mlm_ln.g"], w["mlm_ln.b"], eps)
    mlm = mm(h, w["word_embed"].T) + w["mlm_bias"]
    nsp = dense(pooled, w["nsp.w"], w["nsp.b"])

    def ce_sum(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()

    n_mlm = batch_rows * positions.shape[1]
    return ce_sum(mlm, mlm_labels) / n_mlm + ce_sum(nsp, nsp_labels) / batch_rows


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def follow_steps(w, batches, keys, cfg, opt, block_rows, precision="float32",
                 projection=None):
    """Run ``len(batches)`` Adam steps from weights ``w`` (consumed).

    ``batches[t]`` is the step's six whole-batch arrays (tokens, segments,
    valid mask, masked positions, masked-LM labels, next-sentence labels),
    ``keys[t]`` its dropout key.  Returns host numbers only: the loss of
    each step, the norm of every leaf of the first gradient, and the norm
    of every leaf's change over all the steps.  ``projection`` is
    ``(key, k)``: the first gradient's ``k`` seeded random projections
    (``perf.harness.projections``) are returned as well."""
    batch_rows = int(batches[0][0].shape[0])
    if batch_rows % block_rows:
        raise ValueError(f"{batch_rows} rows do not divide into blocks of "
                         f"{block_rows}")
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        block_loss, cfg=cfg, batch_rows=batch_rows, rows=block_rows,
        precision=precision)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], \
        opt["learning_rate"]

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(w, g, m, v, t):
        lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree_util.tree_map(
            lambda v_, g_: b2 * v_ + (1 - b2) * jnp.square(g_), v, g)
        w = jax.tree_util.tree_map(
            lambda w_, m_, v_: w_ - lr_t * m_ / (jnp.sqrt(v_) + eps), w, m, v)
        return w, m, v

    w0 = jax.tree_util.tree_map(jnp.copy, w)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad, first_proj = [], None, None
    for t, (batch, key) in enumerate(zip(batches, keys)):
        loss, grads = 0.0, None
        for r0 in range(0, batch_rows, block_rows):
            part, g = grad_fn(w, batch, key, jnp.int32(r0))
            loss = loss + part
            grads = g if grads is None else add(grads, g)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {k: float(x) for k, x in
                          jax.jit(_leaf_norms)(grads).items()}
            if projection is not None:
                from ..harness.projections import project
                first_proj = [float(x) for x in project(
                    grads, projection[0], projection[1])]
        w, m, v = adam(w, grads, m, v, jnp.float32(t + 1))
    delta = jax.jit(lambda a, b: _leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(w, w0)
    return {"losses": losses, "grad_norms": first_grad,
            "grad_projections": first_proj,
            "delta_norms": {k: float(x) for k, x in delta.items()}}
