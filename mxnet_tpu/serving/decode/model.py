"""Causal-LM decode model — one math implementation for prefill AND step.

The decode runtime has two compiled surfaces that MUST agree numerically:
the prefill (whole padded prompt, emits per-layer K/V for the cache) and
the per-token decode step (reads K/V back through the paged cache).  Both
are built here from the same pure-jax layer functions; :class:`CausalLM`
is a ``HybridBlock`` whose ``hybrid_forward`` delegates to the shared
prefill function via ``ndarray.invoke_fn`` — so the prefill rides the
CachedOp path (``HybridBlock.compile_for`` / ``compile_grid`` warm the 2-D
batch x seqlen ladder) while the fused decode step is a raw donated jit
built from the very same per-layer math.

**The row-stable contract.**  Continuous batching promises per-request
outputs bitwise-identical to a solo run of the same request — otherwise a
request's result depends on who it happened to share a batch with, and
"replay this request" stops being a debugging tool.  XLA does NOT give
that for free: a plain ``(B, U) @ (U, V)`` matmul tiles differently per
batch size, so row 0 of a batch-8 product differs in final bits from the
batch-1 product.  Every contraction here therefore goes through
:func:`rowdot` (broadcast-multiply + reduce over the contraction axis:
per-row reduction order is independent of the batch dimension), and
attention contracts through batch-dimension ``einsum``s (``dot_general``
batch dims — per-row by construction).  Trading MXU-shaped matmuls for
row stability costs FLOP efficiency; on a real TPU deployment where
cross-batch bit-identity can be relaxed, swap :func:`rowdot` for a plain
``@`` and the parity tests for tolerance checks — everything else holds.
"""
from __future__ import annotations

import math

import numpy as np

from ...gluon.block import HybridBlock
from ...ndarray import NDArray, invoke_fn

__all__ = ["CausalLM", "get_decode_model", "rowdot", "sample_math"]


def rowdot(x, w):
    """Bitwise row-stable contraction ``x (..., U) . w (U, V) -> (..., V)``.

    Broadcast-multiply + reduce keeps each output row's accumulation order
    independent of every *other* leading-dim index — the property a plain
    matmul loses to tiling (see module docstring)."""
    return (x[..., :, None] * w).sum(axis=-2)


def _ln(x, g, b, eps=1e-5):
    import jax
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _gelu(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def commit_destinations(S, lengths, tables, page_size):
    """``(page, offset) (B, S)`` at which a prefill's position ``j`` of row
    ``b`` is stored: the page ``tables[b, j // page_size]``, or the trash
    page where ``j`` is padding past ``lengths[b]``."""
    import jax.numpy as jnp
    B = lengths.shape[0]
    j = jnp.arange(S)[None, :]
    dest_page = jnp.where(
        j < lengths[:, None],
        jnp.take_along_axis(tables, j // page_size, axis=1), 0)
    return dest_page, jnp.broadcast_to(j % page_size, (B, S))


def sample_math(logits, keys, steps, temps):
    """Per-row next-token choice on a deterministic per-request key
    stream: greedy at ``temp == 0``, Gumbel-max temperature sampling
    otherwise.  ``keys (B, 2) uint32`` are request base keys and
    ``steps (B,) int32`` the per-request token index — folding inside
    the program keeps the stream a pure function of (request seed,
    token index), independent of batch composition or scheduling."""
    import jax
    import jax.numpy as jnp
    greedy = jnp.argmax(logits, -1).astype("int32")

    def with_gumbel(_):
        folded = jax.vmap(jax.random.fold_in)(keys, steps)
        u = jax.vmap(lambda kk: jax.random.uniform(
            kk, (logits.shape[-1],), minval=1e-7, maxval=1.0))(folded)
        g = -jnp.log(-jnp.log(u))
        t = jnp.where(temps > 0, temps, 1.0)[:, None]
        sampled = jnp.argmax(logits / t + g, -1).astype("int32")
        return jnp.where(temps > 0, sampled, greedy)

    # all-greedy batches skip the Gumbel streams entirely (threefry
    # is the hot op at decode shapes); any sampled row takes the
    # full branch, whose per-row folds are untouched — either way
    # the returned tokens are bitwise the unconditional computation
    return jax.lax.cond(jnp.any(temps > 0), with_gumbel,
                        lambda _: greedy, None)


class CausalLM(HybridBlock):
    """Decoder-only transformer (pre-LN, learned positions, tied embedding).

    ``forward(tokens, lengths)`` — tokens ``(B, S)`` int32 padded to the
    seq bucket, lengths ``(B,)`` int32 — returns
    ``(last_logits (B, vocab), kv (2, layers, B, S, heads, head_dim))``:
    the next-token logits at each row's last valid position plus every
    layer's K/V for the paged-cache commit.  Only the causal mask is
    needed in prefill: padded *keys* can only influence padded *queries*,
    and the K/V of padded positions is routed to the cache's trash page by
    the commit program.

    The decode hot path never touches this class' forward directly — the
    runtime compiles the prefill through the CachedOp ladder and builds its
    fused step program from :meth:`step_program`.
    """

    def __init__(self, vocab_size=512, units=128, num_layers=2, num_heads=4,
                 max_length=128, hidden_size=None, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError(f"units={units} not divisible by "
                             f"num_heads={num_heads}")
        self.vocab_size = int(vocab_size)
        self.units = int(units)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = self.units // self.num_heads
        self.max_length = int(max_length)
        self.hidden_size = int(hidden_size or 4 * units)
        u, hid = self.units, self.hidden_size
        get = self.params.get
        self.embed = get("embed", shape=(self.vocab_size, u), init="normal")
        self.pos_embed = get("pos_embed", shape=(self.max_length, u),
                             init="normal")
        self.lnf_g = get("lnf_g", shape=(u,), init="ones")
        self.lnf_b = get("lnf_b", shape=(u,), init="zeros")
        for i in range(self.num_layers):
            setattr(self, f"l{i}_ln1_g", get(f"l{i}_ln1_g", shape=(u,),
                                             init="ones"))
            setattr(self, f"l{i}_ln1_b", get(f"l{i}_ln1_b", shape=(u,),
                                             init="zeros"))
            setattr(self, f"l{i}_wqkv", get(f"l{i}_wqkv", shape=(u, 3 * u),
                                            init="normal"))
            setattr(self, f"l{i}_bqkv", get(f"l{i}_bqkv", shape=(3 * u,),
                                            init="zeros"))
            setattr(self, f"l{i}_wo", get(f"l{i}_wo", shape=(u, u),
                                          init="normal"))
            setattr(self, f"l{i}_bo", get(f"l{i}_bo", shape=(u,),
                                          init="zeros"))
            setattr(self, f"l{i}_ln2_g", get(f"l{i}_ln2_g", shape=(u,),
                                             init="ones"))
            setattr(self, f"l{i}_ln2_b", get(f"l{i}_ln2_b", shape=(u,),
                                             init="zeros"))
            setattr(self, f"l{i}_w1", get(f"l{i}_w1", shape=(u, hid),
                                          init="normal"))
            setattr(self, f"l{i}_b1", get(f"l{i}_b1", shape=(hid,),
                                          init="zeros"))
            setattr(self, f"l{i}_w2", get(f"l{i}_w2", shape=(hid, u),
                                          init="normal"))
            setattr(self, f"l{i}_b2", get(f"l{i}_b2", shape=(u,),
                                          init="zeros"))
        self._param_order = sorted(self._reg_params)
        self._scale = 1.0 / math.sqrt(self.head_dim)

    # ------------------------------------------------------------ pure math
    def _params_dict(self, leaves):
        return dict(zip(self._param_order, leaves))

    def param_leaves(self):
        """Concrete jax arrays in ``_param_order`` — the argument list the
        raw step/commit programs take (the CachedOp path passes them through
        the block machinery instead)."""
        return [self._reg_params[n].data()._data for n in self._param_order]

    def _layer(self, p, i, h, attend):
        """One pre-LN transformer layer.  ``attend(i, q, k, v)`` supplies the
        attention context — the ONLY piece that differs between prefill
        (dense causal) and decode step (paged-cache gather), so everything
        else is provably shared math."""
        import jax.numpy as jnp
        a = _ln(h, p[f"l{i}_ln1_g"], p[f"l{i}_ln1_b"])
        qkv = rowdot(a, p[f"l{i}_wqkv"]) + p[f"l{i}_bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        ctx = attend(i, q * self._scale, k, v)
        h = h + rowdot(ctx, p[f"l{i}_wo"]) + p[f"l{i}_bo"]
        m = _ln(h, p[f"l{i}_ln2_g"], p[f"l{i}_ln2_b"])
        return h + rowdot(_gelu(rowdot(m, p[f"l{i}_w1"]) + p[f"l{i}_b1"]),
                          p[f"l{i}_w2"]) + p[f"l{i}_b2"]

    def prefill_math(self, p, tokens, lengths):
        """Pure prefill: ``(last_logits, kv)`` — see class docstring."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        H, D = self.num_heads, self.head_dim
        h = p["embed"][tokens] + p["pos_embed"][:S][None]
        causal = jnp.tril(jnp.ones((S, S), bool))
        ks, vs = [], []

        def attend(_i, q, k, v):
            q = q.reshape(B, S, H, D)
            k = k.reshape(B, S, H, D)
            v = v.reshape(B, S, H, D)
            ks.append(k)
            vs.append(v)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            s = jnp.where(causal[None, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(B, S, -1)

        for i in range(self.num_layers):
            h = self._layer(p, i, h, attend)
        hf = _ln(h, p["lnf_g"], p["lnf_b"])
        last = hf[jnp.arange(B), lengths - 1]
        logits = rowdot(last, p["embed"].T)
        return logits, jnp.stack([jnp.stack(ks), jnp.stack(vs)])

    def step_program(self, p, tokens, positions, tables, pools, pages):
        """Pure fused decode step for one token per row, by the runtime's
        protocol: ``(logits, pools, extras)``.

        Writes each row's new K/V into its page (``tables`` routes padded
        rows to trash page 0), gathers the row's whole paged context
        (fixed length ``max_pages * page_size`` — constant shape is what
        keeps one compiled program per batch bucket AND makes the math
        identical regardless of physical page placement), and returns the
        next-token logits with the updated pools.

        ``pages`` is the cache's :class:`~mxnet_tpu.serving.decode.
        kv_format.PageFormat`: it quantizes the new token row at the write
        and dequantizes the gathered context before the attention einsums
        where the pools are quantized; both are row-stable, so per-row
        bitwise independence of batch composition holds in every format."""
        import jax
        import jax.numpy as jnp
        B = tokens.shape[0]
        H, D = self.num_heads, self.head_dim
        page_size = pages.page_size
        lctx = tables.shape[1] * page_size
        h = p["embed"][tokens] + p["pos_embed"][positions]
        wp = jnp.take_along_axis(tables, (positions // page_size)[:, None],
                                 axis=1)[:, 0]
        woff = positions % page_size
        mask = jnp.arange(lctx)[None, :] <= positions[:, None]

        def attend(i, q, k, v):
            nonlocal pools
            q = q.reshape(B, H, D)
            pools = pages.write(pools, i, wp, woff,
                                (k.reshape(B, H, D), v.reshape(B, H, D)))
            kg, vg = pages.read(pools, i, tables)
            s = jnp.einsum("bhd,blhd->bhl", q, kg)
            s = jnp.where(mask[:, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhl,blhd->bhd", pr, vg).reshape(B, -1)

        for i in range(self.num_layers):
            h = self._layer(p, i, h, attend)
        hf = _ln(h, p["lnf_g"], p["lnf_b"])
        return rowdot(hf, p["embed"].T), pools, ()

    def verify_program(self, p, tokens, positions, n_draft, tables, pools,
                       pages):
        """Pure fused speculative *verify*: ``K+1`` tokens per row in one
        program.  ``tokens (B, K+1)`` is ``[cur, d_1 .. d_K]`` — the row's
        current token followed by its drafted continuation, padded past
        ``n_draft (B,)`` — at positions ``positions + (0 .. K)``.

        Per layer the program scatters all ``K+1`` candidate K/V rows into
        the row's own reserved pages (offsets past ``n_draft``, or past the
        page-table range, are routed to trash page 0), gathers the same
        fixed-length paged context the single-token step gathers, and
        attends with a causal mask *extension*: query offset ``j`` sees
        context positions ``<= positions + j`` — which includes the
        candidate K/V written at offsets ``< j`` this very call.  By
        induction over offsets and layers, offset ``j``'s logits are
        bitwise what the non-speculative step would produce after emitting
        ``d_1 .. d_j`` — the property the deterministic acceptance rule in
        the runtime's verify program builds on.  Returns
        ``(logits (B, K+1, V), pools)``.

        Rejected candidates need no explicit rollback: their K/V sits at
        positions strictly greater than the row's post-verify position, so
        every later query masks them until they are overwritten by the
        next boundary's writes at those same positions."""
        import jax
        import jax.numpy as jnp
        B, K1 = tokens.shape
        H, D = self.num_heads, self.head_dim
        page_size = pages.page_size
        n_tab = tables.shape[1]
        lctx = n_tab * page_size
        offs = jnp.arange(K1, dtype="int32")[None, :]
        pos = positions[:, None] + offs                       # (B, K+1)
        h = (p["embed"][tokens]
             + p["pos_embed"][jnp.minimum(pos, self.max_length - 1)])
        page_idx = pos // page_size
        owned = jnp.take_along_axis(
            tables, jnp.minimum(page_idx, n_tab - 1), axis=1)
        # invalid offsets (padding past n_draft, or positions past the
        # row's reserved pages) write to trash page 0 — never into a
        # neighbour's (or this row's own committed) pages
        valid = (offs <= n_draft[:, None]) & (page_idx < n_tab)
        wp = jnp.where(valid, owned, 0)
        woff = pos % page_size
        mask = jnp.arange(lctx)[None, None, :] <= pos[:, :, None]

        def attend(i, q, k, v):
            nonlocal pools
            q = q.reshape(B, K1, H, D)
            pools = pages.write(
                pools, i, wp, woff,
                (k.reshape(B, K1, H, D), v.reshape(B, K1, H, D)))
            kg, vg = pages.read(pools, i, tables)
            s = jnp.einsum("bqhd,blhd->bhql", q, kg)
            s = jnp.where(mask[:, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhql,blhd->bqhd", pr, vg).reshape(B, K1, -1)

        for i in range(self.num_layers):
            h = self._layer(p, i, h, attend)
        hf = _ln(h, p["lnf_g"], p["lnf_b"])
        return rowdot(hf, p["embed"].T), pools

    sample_math = staticmethod(sample_math)

    # ------------------------------------- what the runtime and cache read
    #: prompts one prefill call may hold (None: as many as a step)
    max_prefill_batch = None

    def cache_layout(self):
        """Two pools (keys, values) whose row is the whole token row
        ``heads * head_dim`` in float32; quantizable; under a mesh the row
        axis is split by heads."""
        row = self.num_heads * self.head_dim
        return {"layers": self.num_layers,
                "pools": (("k", row, "float32"), ("v", row, "float32")),
                "quantizable": True, "shard_heads": self.num_heads,
                "max_length": self.max_length}

    def prefill_state(self, b, s):
        """Shape and dtype of the K/V :meth:`prefill_math` emits."""
        return (2, self.num_layers, b, s, self.num_heads,
                self.head_dim), "float32"

    def commit_program(self, kv, lengths, tables, pools, pages):
        """Store the prefill's ``kv`` in the pools at the pages ``tables``
        names (positions past ``lengths`` go to the trash page): one write
        a layer, as the step writes (a scatter over all layers at once
        makes the compiler relayout both whole pools)."""
        dest_page, dest_off = commit_destinations(
            kv.shape[3], lengths, tables, pages.page_size)
        for i in range(self.num_layers):
            pools = pages.write(pools, i, dest_page, dest_off,
                                (kv[0, i], kv[1, i]))
        return pools

    # ------------------------------------------------------- gluon frontend
    def hybrid_forward(self, F, tokens, lengths, **params):
        if not isinstance(tokens, NDArray) and not hasattr(tokens, "_data"):
            raise NotImplementedError(
                "CausalLM has no symbolic frontend (export is not "
                "supported); the decode runtime compiles it through "
                "compile_grid / the CachedOp path instead")
        leaves = [params[n] for n in self._param_order]

        def pure(tok, ln_, *leaf_vals):
            return self.prefill_math(self._params_dict(leaf_vals),
                                     tok, ln_)

        return tuple(invoke_fn(pure, [tokens, lengths] + leaves,
                               op_name="causal_lm_prefill"))


_DECODE_CONFIGS = {
    "decode_tiny": dict(units=64, num_layers=2, num_heads=2),
    "decode_small": dict(units=128, num_layers=2, num_heads=4),
    "decode_base": dict(units=256, num_layers=4, num_heads=8),
}


def get_decode_model(model_name="decode_small", vocab_size=512,
                     max_length=128, **kwargs):
    """Named :class:`CausalLM` configs (the decode analog of
    ``models.get_bert_model``)."""
    cfg = dict(_DECODE_CONFIGS[model_name])
    cfg.update(kwargs)
    return CausalLM(vocab_size=vocab_size, max_length=max_length, **cfg)
