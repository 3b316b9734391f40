"""Byte-level decoder with EVA attention (the ``evabyte`` family's block, as
EvaByte publishes it: "Efficient Attention via Control Variates" made
deterministic) — a sixth block beside :class:`CausalLM`,
:class:`LatentMoELM`, :class:`HybridSSMMoELM`, :class:`WindowMoELM` and
:class:`LinearMoELM` behind the same runtime, scheduler and cache.  Dense: no
expert layer.

Every layer is ``x <- x + attn(RMSNorm(x)) Wo``, ``x <- x + swiglu(RMSNorm'
(x))`` on a float32 residual, the norms with a unit offset (``x * rsqrt(mean
x^2 + eps) * (1 + g)``), rotary multi-head attention (as many K/V heads as
query heads, rotate-half over the whole head, one base).  What is its own is
what a query may read.  The sequence is cut into **windows** of ``window_size``
positions and **chunks** of ``chunk_size``; with ``s = head_dim ** -0.5`` and
two learned vectors a head, ``phi`` and ``mu``:

    chunk j (positions c j .. c j + c - 1):
      kbar_j = sum_m softmax_m(s k_m . phi) k_m + mu     its summary key
      vbar_j = sum_m softmax_m(s k_m . mu)  v_m          its summary value
    query t, w = t // W:
      columns = {k_m : w W <= m <= t}  U  {kbar_j : j < w W / c}
      ONE softmax over all columns of s q_t . column;  o_t = sum p v

so a query attends exactly and causally inside its OWN window and, in the same
softmax, over one summary a chunk of every window BEFORE its own.  Keys are
rotated before they are pooled, at their own positions; a summary carries no
position of its own.  A window closes whole (``W / c`` chunks), so no chunk is
partial where it can be seen, and a row in its first window has no summary
column.

**What a sequence keeps**, two kinds of attention state under one allocator:

- a **ring** a layer and slot (``cache_layout()``'s ``state`` section,
  ``kv_format.SlotState``) of the OPEN window's exact keys and values,
  ``(window, heads, head_dim)`` each, bounded whatever the context.  Position
  ``t`` writes entry ``t mod W``; entry ``e`` is live for a query at ``t`` iff
  ``e <= t mod W``: "same window as the query", not ``WindowMoELM``'s "the last
  W", so what the closed window (or the slot's last owner) left past ``t mod
  W`` is masked, not read;
- **summary pages**: K and V pools (``kbar`` / ``vbar``) whose ROW stands for
  ``chunk_size`` tokens (the layout's ``row_tokens``): a page of 16 rows holds
  256 positions' summaries, and a context of 12,288 reserves 48 pages where a
  row a token would take 768.  A step writes row ``t // c`` unconditionally
  from the chunk's ``c`` ring entries: the last such write, at ``t mod c = c -
  1``, is the whole chunk's, and no earlier one can be seen.

The step's attention is one call a layer through the page format's door
(``kv_format.PageFormat.attend_window``): lowered for the chip, ONE kernel
(``ops.pallas_kernels.eva_attention``) that reads the live rows' ring entries
``0 .. t mod W`` and the summary pages of their closed windows out of the
whole pools where they lie, under one softmax, and nothing of a padded row;
lowered for the CPU, the definition: the row's whole ring and its reserved
summary rows gathered and :meth:`EvaLM.attend_row`'s one masked softmax over
both.  **Prefill never holds a ``(heads, S, S)`` array**:
it goes by query blocks of at most one window (:attr:`query_block` queries at
a time), each over its own window's keys and the summaries of the windows
before; the commit hands the slot the open window's keys and values at their
ring entries and writes the summary of EVERY complete chunk of the prompt,
the open window's too (no step makes them again).

The head is ``num_pred_heads`` heads of ``vocab_size`` logits (multibyte
prediction): head ``i`` scores the byte ``i + 1`` ahead.  Head 0 is served;
the others' first choices leave the step as ``drafts`` among its counts (so
the program computes every head), and nothing drafts from them yet.

Precision and contract as its siblings: weights, ring entries and summary rows
are ``dtype`` (bfloat16 as served), the matrix products in that dtype with
float32 accumulation; the residual, norms, rotary angles, both pooling
softmaxes (with their sums, elementwise in float32), the attention softmax and
the logits float32.  Held to the plain reference (``perf/reference/
evabyte.py``) within the tolerances ``tests/test_eva_lm.py`` writes down.

What the block refuses, each with a sentence (none needs code here): a drafter
(there is no verify program: a rejected draft's entries would have to be taken
out of the ring and its chunk pooled again), quantized pools, and a mesh (slot
pools are not sharded).  Prefix sharing is a no-op for it (the ring at a
prefix boundary is in no page): the cache makes no lookup and counts
``decode.prefix.skipped``.
"""
from __future__ import annotations

import math

import numpy as np

from ...gluon.block import HybridBlock
from ...ndarray import NDArray, invoke_fn
from ...telemetry import bus as _tel
from .latent_moe import _dot, _einsum, _rms, _swiglu
from .model import commit_destinations, sample_math

__all__ = ["EvaLM"]


class EvaLM(HybridBlock):
    """Decoder-only transformer of EVA-attention layers; see the module
    docstring.  ``forward(tokens (B, S), lengths (B,))`` returns
    ``(last_logits (B, vocab) float32 [head 0],`` then for each layer in turn
    ``kbar, vbar (B, S // chunk_size, heads, head_dim), ring_k, ring_v (B,
    window_size, heads, head_dim))`` for the runtime's commit program: a
    layer's state leaves the program as the layer made it (stacked over the
    layers, a 10,240-byte prompt's 0.7 GB was held twice: sandbox compile,
    PR 43)."""

    def __init__(self, vocab_size=320, hidden_size=64, num_layers=2,
                 num_attention_heads=4, intermediate_size=128,
                 window_size=32, chunk_size=4, num_pred_heads=8,
                 rope_theta=1e5, norm_eps=1e-5, max_length=256,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self.vocab_size, self.units = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.heads = int(num_attention_heads)
        self.window, self.chunk = int(window_size), int(chunk_size)
        self.pred_heads = int(num_pred_heads)
        if self.units % self.heads or (self.units // self.heads) % 2:
            raise ValueError(
                f"hidden_size={self.units} over {self.heads} heads is not a "
                f"whole, even head width: rotate-half pairs its dimensions")
        if self.window % self.chunk or self.chunk < 1:
            raise ValueError(
                f"window_size={self.window} is not whole chunks of "
                f"chunk_size={self.chunk}: a window closes whole")
        self.head_dim = self.units // self.heads
        j = np.arange(0, self.head_dim, 2, dtype="float64") / self.head_dim
        self._inv_freq = (float(rope_theta) ** -j).astype("float32")
        #: queries a block of the prompt's attention holds scores for
        self.query_block = math.gcd(self.window, 512)
        self.eps = float(norm_eps)
        self.max_length = int(max_length)
        self.dtype = str(dtype)
        u, wd, f = self.units, self.dtype, int(intermediate_size)

        def reg(name, shape, init="normal", dtype=wd):
            setattr(self, name, self.params.get(name, shape=shape, init=init,
                                                dtype=dtype))

        reg("embed", (self.vocab_size, u))
        # head i's logits are columns i * vocab .. (i + 1) * vocab - 1
        reg("head", (u, self.pred_heads * self.vocab_size))
        # a norm's parameter is its OFFSET g: the gain is 1 + g
        reg("norm_f", (u,), "zeros", "float32")
        for i in range(self.num_layers):
            p = f"l{i}_"
            reg(p + "norm_attn", (u,), "zeros", "float32")
            for m in ("wq", "wk", "wv", "wo"):
                reg(p + m, (u, u))
            # the two learned vectors a head (adaptive_phi, adaptive_mu_k)
            reg(p + "phi", (self.heads, self.head_dim), dtype="float32")
            reg(p + "mu", (self.heads, self.head_dim), dtype="float32")
            reg(p + "norm_ffn", (u,), "zeros", "float32")
            reg(p + "wg", (u, f))
            reg(p + "wu", (u, f))
            reg(p + "wd", (f, u))
        self._param_order = sorted(self._reg_params)

    # ------------------------------------------------- what the runtime reads
    #: one prompt a prefill call: a prompt is a window or more of bytes
    max_prefill_batch = 1

    def cache_layout(self):
        """Summary pages whose row stands for ``chunk_size`` tokens
        (``row_tokens``: a fact of the layout, not a setting), one layer of
        the ``kbar`` and ``vbar`` pools a layer of the block; and a slot's
        rings of the open window's keys and values.  Rows and ring entries
        are stored by head, ``(heads, head_dim)``: the step's kernel reads a
        block of them as ``(columns x heads, head_dim)`` rows, a view and
        not a copy because the heads are whole sublane tiles, and the CPU's
        form multiplies and reduces them so (splitting a flat row of a
        gathered ring into heads was a copy of the ring: sandbox compile,
        PR 43).  Not quantizable, not sharded."""
        hd = (self.heads, self.head_dim)
        return {"layers": self.num_layers,
                "pools": (("kbar", self.units, self.dtype),
                          ("vbar", self.units, self.dtype)),
                "row_shape": hd, "row_tokens": self.chunk,
                "quantizable": False, "shard_heads": None,
                "max_length": self.max_length,
                "state": {"layers": self.num_layers,
                          "arrays": (("ring_k", (self.window,) + hd,
                                      self.dtype),
                                     ("ring_v", (self.window,) + hd,
                                      self.dtype))}}

    def prefill_state(self, b, s):
        """Shapes and dtypes of what :meth:`prefill_math` emits behind the
        logits, a layer after another: the summaries of the prompt's chunks
        (keys, values), the open window's rings (keys, values)."""
        hd = (self.heads, self.head_dim)
        rows = ((b, s // self.chunk) + hd, self.dtype)
        ring = ((b, self.window) + hd, self.dtype)
        return (rows, rows, ring, ring) * self.num_layers

    def _params_dict(self, leaves):
        return dict(zip(self._param_order, leaves))

    def param_leaves(self):
        return [self._reg_params[n].data()._data for n in self._param_order]

    # ------------------------------------------------------------ pure math
    def _norm(self, x, g):
        return _rms(x, 1.0 + g, self.eps)

    def _rope(self, x, positions):
        """Rotate ``x (..., heads, head_dim)`` at ``positions (...)``,
        pairing dimension ``j`` with ``j + head_dim / 2`` (rotate-half)."""
        import jax.numpy as jnp
        half = self.head_dim // 2
        ang = positions[..., None, None].astype(jnp.float32) \
            * jnp.asarray(self._inv_freq)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def _qkv(self, p, i, a, positions):
        """``(q float32, k, v in the cache dtype)``, each ``(..., heads,
        head_dim)``, of ``a (..., U)`` at ``positions (...)``."""
        import jax
        pre = f"l{i}_"
        shape = a.shape[:-1] + (self.heads, self.head_dim)
        with jax.named_scope("attn.proj"):
            q = self._rope(_dot(a, p[pre + "wq"]).reshape(shape), positions)
            k = self._rope(_dot(a, p[pre + "wk"]).reshape(shape), positions)
            v = _dot(a, p[pre + "wv"]).reshape(shape)
        return q, k.astype(self.dtype), v.astype(self.dtype)

    def pool(self, p, i, k, v):
        """The summaries of chunks: ``k``, ``v (..., chunk_size, heads,
        head_dim)`` (stored precision) -> ``(kbar, vbar) (..., heads,
        head_dim)`` in the cache dtype.  Both pooling logits read the KEYS
        (``phi`` pools the keys, ``mu`` the values and joins the pooled
        key); softmaxes and sums elementwise in float32."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("eva.pool"):
            kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
            phi, mu = p[f"l{i}_phi"], p[f"l{i}_mu"]
            scale = self.head_dim ** -0.5
            wk = jax.nn.softmax((kf * phi).sum(-1) * scale, axis=-2)
            wv = jax.nn.softmax((kf * mu).sum(-1) * scale, axis=-2)
            kbar = (wk[..., None] * kf).sum(-3) + mu
            vbar = (wv[..., None] * vf).sum(-3)
            return kbar.astype(self.dtype), vbar.astype(self.dtype)

    def attend(self, q, k, v, live, sk, sv, seen):
        """ONE softmax of ``q (B, Q, heads, head_dim)`` over the exact keys
        ``k``, ``v (B, L, heads, head_dim)`` where ``live (B, Q, L)`` allows
        and the summaries ``sk``, ``sv (B, J, heads, head_dim)`` where ``seen
        (B, Q, J)`` does: the prefill's form, on the MXU.  Returns the heads'
        outputs ``(B, Q, heads, head_dim)`` float32.  The scores are joined,
        never the keys."""
        import jax
        import jax.numpy as jnp
        L, dt = k.shape[1], self.dtype
        with jax.named_scope("attn.eva"):
            s = jnp.concatenate(
                [_einsum("bqhd,blhd->bhql", q, x, dt) for x in (k, sk)],
                axis=-1) * self.head_dim ** -0.5
            mask = jnp.concatenate([live, seen], axis=-1)
            s = jnp.where(mask[:, None], s, -1e30)
            e = jnp.exp(s - s.max(-1, keepdims=True))
            pr = e / e.sum(-1, keepdims=True)
            return _einsum("bhql,blhd->bqhd", pr[..., :L], v, dt) \
                + _einsum("bhql,blhd->bqhd", pr[..., L:], sv, dt)

    def attend_row(self, q, k, v, live, sk, sv, seen):
        """:meth:`attend` for ONE row's one query, ``q (heads, head_dim)``
        float32 over its ring ``k``, ``v (L, heads, head_dim)`` where ``live
        (L,)`` and its summaries ``sk``, ``sv (J, heads, head_dim)`` where
        ``seen (J,)``: the DEFINITION of the step's attention, and its form
        where a step program is lowered for the CPU (``PageFormat.
        attend_window`` hands it a row's whole ring and every reserved
        summary row; lowered for the chip the step is ``ops.pallas_kernels.
        eva_attention``, which reads the live ones alone and is held to this
        by ``tests/test_eva_attention_kernel.py``).  Scores and context are
        multiply-and-reduce by head, each operand read once in the stored
        precision and every sum float32, the scores laid out ``(columns,
        heads)`` as the keys are.  Returns ``(heads, head_dim)`` float32."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        with jax.named_scope("attn.eva"):
            s = jnp.concatenate([(x.astype(f32) * q).sum(-1)
                                 for x in (k, sk)]) * self.head_dim ** -0.5
            s = jnp.where(jnp.concatenate([live, seen])[:, None], s, -1e30)
            e = jnp.exp(s - s.max(0))
            pr = (e / e.sum(0))[..., None]
            L = k.shape[0]
            return (pr[:L] * v.astype(f32)).sum(0) \
                + (pr[L:] * sv.astype(f32)).sum(0)

    def attend_prompt(self, q, k, v, kbar, vbar):
        """EVA attention of a whole padded prompt of whole windows, ``q``,
        ``k``, ``v (B, S, heads, head_dim)`` and the chunks' summaries
        ``kbar``, ``vbar (B, S / chunk_size, heads, head_dim)``: a map over
        blocks of :attr:`query_block` queries, each causal inside its window
        and over the summaries of the windows before.  Returns ``(B, S,
        heads, head_dim)`` float32."""
        import jax
        import jax.numpy as jnp
        B, S = q.shape[:2]
        W, Q = self.window, self.query_block
        a, e = jnp.arange(Q), jnp.arange(W)
        chunks = jnp.arange(S // self.chunk)

        def block(args):
            qb, n = args
            w = (n * Q) // W
            kw, vw = (jax.lax.dynamic_slice_in_dim(x, w * W, W, axis=1)
                      for x in (k, v))
            live = (w * W + e)[None, :] <= (n * Q + a)[:, None]
            seen = jnp.broadcast_to(
                chunks[None, :] < w * (W // self.chunk), (Q, chunks.size))
            return self.attend(qb, kw, vw,
                               jnp.broadcast_to(live, (B,) + live.shape),
                               kbar, vbar,
                               jnp.broadcast_to(seen, (B,) + seen.shape))

        qs = q.reshape((B, S // Q, Q) + q.shape[2:]).swapaxes(0, 1)
        o = jax.lax.map(block, (qs, jnp.arange(S // Q)))
        return o.swapaxes(0, 1).reshape(q.shape)

    def _ring_of(self, rows, lengths):
        """The open window's ring as of ``lengths``: entry ``e`` holds the
        row of position ``(length // W) W + e`` where the prompt has it, else
        zeros (masked until a step writes it).  ``rows (B, S, heads,
        head_dim)`` -> ``(B, window, heads, head_dim)``."""
        import jax.numpy as jnp
        W = self.window
        pos = ((lengths // W) * W)[:, None] + jnp.arange(W)[None, :]
        ring = jnp.take_along_axis(
            rows, jnp.clip(pos, 0, rows.shape[1] - 1)[:, :, None, None],
            axis=1)
        return jnp.where((pos < lengths[:, None])[:, :, None, None], ring, 0)

    def _mlp(self, p, i, h):
        import jax
        pre = f"l{i}_"
        with jax.named_scope("ffn.dense"):
            return h + _swiglu(self._norm(h, p[pre + "norm_ffn"]),
                               p[pre + "wg"], p[pre + "wu"], p[pre + "wd"])

    def head_logits(self, p, h):
        """Every prediction head's logits of final hidden rows ``h (B, U)``:
        ``(B, num_pred_heads, vocab)`` float32."""
        import jax
        with jax.named_scope("head"):
            logits = _dot(self._norm(h, p["norm_f"]), p["head"])
        return logits.reshape(h.shape[0], self.pred_heads, self.vocab_size)

    def prefill_hidden(self, p, tokens, lengths):
        """``(h_last (B, U), state)``: the residual at each row's last
        position, before the final norm, and the state :meth:`prefill_math`
        emits, ``(kbar, vbar, ring_k, ring_v)`` a layer."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        W, c = self.window, self.chunk
        pad = -S % W
        if pad:
            # whole windows: padding is causally after every real position
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        Sp = S + pad
        h = p["embed"][tokens].astype(jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(Sp, dtype=jnp.int32)[None], (B, Sp))
        state = []
        for i in range(self.num_layers):
            a = self._norm(h, p[f"l{i}_norm_attn"])
            q, k, v = self._qkv(p, i, a, pos)
            by_chunk = (B, Sp // c, c) + k.shape[2:]
            kbar, vbar = self.pool(p, i, k.reshape(by_chunk),
                                   v.reshape(by_chunk))
            o = self.attend_prompt(q, k, v, kbar, vbar)
            with jax.named_scope("attn.proj"):
                h = h + _dot(o.reshape(B, Sp, -1), p[f"l{i}_wo"])
            # a window of rows at a time: the SwiGLU's two float32
            # intermediates of a 10,240-byte prompt are 0.9 GB whole
            h = jax.lax.map(lambda x, i=i: self._mlp(p, i, x),
                            h.reshape(-1, W, h.shape[-1])).reshape(h.shape)
            # the layer's state is made before the next layer starts: left
            # free, the chip's compiler puts these gathers off to the end of
            # the program and keeps every layer's keys and values of the
            # whole prompt until then (0.17 GB a layer: sandbox compile, PR
            # 43), which the pools leave no room for
            h, mine = jax.lax.optimization_barrier((h, (
                kbar[:, :S // c], vbar[:, :S // c],
                self._ring_of(k, lengths), self._ring_of(v, lengths))))
            state += mine
        return h[jnp.arange(B), lengths - 1], tuple(state)

    def prefill_math(self, p, tokens, lengths):
        """Pure prefill: ``(last_logits [head 0], *state)`` — see the class
        docstring."""
        last, state = self.prefill_hidden(p, tokens, lengths)
        return (self.head_logits(p, last)[:, 0],) + state

    def step_program(self, p, tokens, positions, tables, pools, pages):
        """Pure fused decode step, one byte a row.  ``tables`` ends with each
        row's state slot (``pages.addresses``).  Every layer writes the
        byte's K/V into entry ``position mod W`` of the slot's ring, pools
        the chunk that holds the position from its ``chunk_size`` ring
        entries (sliced out of the pool: no ring is read for it) into summary
        row ``position // chunk_size`` of the row's pages, and attends over
        the ring (entries ``<= position mod W``) and the row's summaries
        (those of closed windows) under one softmax: ``pages.attend_window``
        on the pools the two writes returned, one kernel a layer on the chip
        and :meth:`attend_row` over the gathered state on the CPU.  Padded
        rows (page table all trash) write to the trash slot and the trash
        page and attend over nothing.
        Returns ``(logits (B, vocab) [head 0], pools, (drafts (B, heads - 1)
        int32: the other heads' first choices, counts (4,) int32: live ring
        entries, live summary rows, windows closed, live rows))``."""
        import jax
        import jax.numpy as jnp
        ptab, srow = pages.addresses(tables)
        W, c = self.window, self.chunk
        B = tokens.shape[0]
        h = p["embed"][tokens].astype(jnp.float32)
        went = positions % W
        first = went // c * c
        sidx = positions // c
        wp = jnp.take_along_axis(ptab, (sidx // pages.page_size)[:, None],
                                 axis=1)[:, 0]
        woff = sidx % pages.page_size
        closed = positions // W * (W // c)
        for i in range(self.num_layers):
            a = self._norm(h, p[f"l{i}_norm_attn"])
            q, k, v = self._qkv(p, i, a, positions)
            with jax.named_scope("attn.eva"):
                pools = pages.state.write_at(pools, i, srow, went, (k, v))
                # the chunk's entries are sliced out of the pool by the
                # row's slot and the chunk's first entry: no ring is read
                chunk = [[jax.lax.dynamic_slice_in_dim(x, first[b], c)
                          for x in pages.state.read(pools, i, srow[b])]
                         for b in range(B)]
            summary = self.pool(p, i, *(jnp.stack(x) for x in zip(*chunk)))
            with jax.named_scope("attn.eva"):
                pools = pages.write(pools, i, wp, woff, summary)
                o = pages.attend_window(pools, i, ptab, srow, positions, q,
                                        self.attend_row)
            h = self._mlp(p, i, h + _dot(o.reshape(B, -1), p[f"l{i}_wo"]))
        logits = self.head_logits(p, h)
        valid = ptab[:, 0] != 0
        counts = jnp.stack([jnp.where(valid, went + 1, 0).sum(),
                            jnp.where(valid, closed, 0).sum(),
                            (valid & (went == W - 1)).sum(),
                            valid.sum()]).astype(jnp.int32)
        return logits[:, 0], pools, (
            jnp.argmax(logits[:, 1:], -1).astype(jnp.int32), counts)

    def commit_program(self, state, lengths, tables, pools, pages):
        """Store a prefill's state, ``(kbar, vbar, ring_k, ring_v)`` a layer:
        the summary of every COMPLETE chunk of the prompt at row ``chunk`` of
        the pages ``tables`` names (a partial last chunk is pooled by the
        steps that fill it), and each layer's ring as the WHOLE ring of the
        row's slot — whatever the slot's last owner left is overwritten or
        masked."""
        ptab, srow = pages.addresses(tables)
        dest_page, dest_off = commit_destinations(
            state[0].shape[1], lengths // self.chunk, ptab, pages.page_size)
        for i in range(self.num_layers):
            kbar, vbar, ring_k, ring_v = state[4 * i:4 * i + 4]
            pools = pages.write(pools, i, dest_page, dest_off, (kbar, vbar))
            pools = pages.state.write(pools, i, srow, (ring_k, ring_v))
        return pools

    sample_math = staticmethod(sample_math)

    @property
    def entry_bytes(self):
        """Device bytes of one position's exact keys and values, or of one
        chunk's summaries, over every layer."""
        import jax.numpy as jnp
        return self.num_layers * 2 * self.units \
            * jnp.dtype(self.dtype).itemsize

    def record_step_extras(self, extras, model):
        """Telemetry from one step's counts (the program's vector of them,
        flat; the drafts lie before the last four): counters
        ``decode.eva.layer_steps`` (layers run), ``decode.eva.ring_rows`` /
        ``decode.eva.summary_rows`` (LIVE ring entries and LIVE summary rows
        the live rows attended over, summed over layers),
        ``decode.eva.summary_rows_written``, ``decode.eva.windows_closed``
        (rows whose step wrote their window's last entry); gauges
        ``decode.eva.live_ring_bytes`` and ``decode.eva.live_summary_rows``
        (what the live sequences hold that a query can see)."""
        ring, summaries, closed, live = (int(x) for x in
                                         np.asarray(extras)[-4:])
        L = self.num_layers
        _tel.count("decode.eva.layer_steps", L, model=model)
        _tel.count("decode.eva.ring_rows", ring * L, model=model)
        _tel.count("decode.eva.summary_rows", summaries * L, model=model)
        _tel.count("decode.eva.summary_rows_written", live * L, model=model)
        _tel.count("decode.eva.windows_closed", closed, model=model)
        _tel.gauge("decode.eva.live_ring_bytes", ring * self.entry_bytes)
        _tel.gauge("decode.eva.live_summary_rows", summaries)

    # ------------------------------------------------------- gluon frontend
    def hybrid_forward(self, F, tokens, lengths, **params):
        if not isinstance(tokens, NDArray) and not hasattr(tokens, "_data"):
            raise NotImplementedError(
                "EvaLM has no symbolic frontend (export is not supported); "
                "the decode runtime compiles it through compile_grid / the "
                "CachedOp path instead")
        leaves = [params[n] for n in self._param_order]

        def pure(tok, ln_, *leaf_vals):
            return self.prefill_math(self._params_dict(leaf_vals), tok, ln_)

        return tuple(invoke_fn(pure, [tokens, lengths] + leaves,
                               op_name="eva_prefill"))
