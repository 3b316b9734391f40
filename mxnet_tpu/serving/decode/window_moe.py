"""Sliding-window / global attention, routed-expert decode model (the
``mimo_v2`` family's block, as MiMo-V2.5 publishes it) — a fourth block
beside :class:`CausalLM`, :class:`LatentMoELM` and :class:`HybridSSMMoELM`
behind the same runtime, scheduler and cache.

Every layer is ``h <- h + attn(RMSNorm(h))``, ``h <- h + mlp(RMSNorm(h))``.
Attention is grouped-query over keys ``head_dim`` wide and values
``v_head_dim`` wide (192 over 128 as published), rotary (rotate-half
pairing) on the first ``partial_rotary_factor`` of each query's and key's
dimensions, values scaled by ``attention_value_scale``; its KIND is the
layer's entry in ``layer_pattern`` (the config's ``hybrid_layer_pattern``):

- ``0`` — **global**: ``num_key_value_heads`` K/V heads, causal softmax
  over the whole context, rotary base ``rope_theta``.  These layers page:
  the cache's K and V pools (rows of different widths) have one layer for
  each, and a step attends over the row's live pages where they lie
  (``PageFormat.attend``: one kernel on the chip, gather + :meth:`attend`
  on the CPU).
- ``1`` — **window**: ``swa_num_key_value_heads`` K/V heads, token ``i``
  reads ``i - window + 1 .. i``, rotary base ``swa_rope_theta``, and a
  learned **sink bias** a query head that joins the softmax's denominator
  and carries no value.  What such a layer keeps of a sequence is bounded
  whatever the context: a **ring** of the last ``window`` tokens' keys and
  values, one row of the cache's *state pools* a slot
  (:meth:`cache_layout`'s ``state`` section, ``kv_format.SlotState``),
  written ONE token a step at ``position mod window``
  (``SlotState.write_at``); which position an entry holds follows from the
  row's position alone, so nothing is stored beside it and an entry left by
  a slot's last owner is masked, not read.  The prefill's commit hands the
  slot the prompt's LAST ``min(length, window)`` tokens, as of the prompt's
  true length.

The MLP is ``moe_layer_freq``'s: ``0`` a dense SwiGLU, ``1`` routed SwiGLU
experts with no shared expert — ``parallel.moe.routed_expert_share`` over
the experts ``held_experts`` (one chip's share), the choice made on
``sigmoid(x W_r) + b`` (a selection bias an expert, ``route_to_held``'s
``select_bias``) and the weights on the scores without it.

**Prefill never holds a ``(heads, S, S)`` array.**  Both kinds go by query
blocks of ``window`` tokens: a window layer's block reads its own and the
block before it (a band, ``O(S * 2 window)`` scores in all), a global
layer's blocks are mapped one after another over all ``S`` keys
(``(heads, window, S)`` scores at a time).

Precision and contract as its siblings: weights, K/V rows and rings are
``dtype`` (bfloat16 as served); products in that dtype with float32
accumulation; the residual stream, norms, rotary angles, softmax with its
sink, router scores and logits float32.  Held to the plain reference
(``perf/reference/mimo_v2.py``) within the tolerances
``tests/test_window_moe_lm.py`` writes down.

What the block refuses, each with a sentence (none needs code here): a
drafter (there is no verify program: a rejected draft's tokens would have
to be taken out of the rings), quantized pools, and a mesh (slot pools are
not sharded).  Prefix sharing is a no-op for it (the ring at a prefix
boundary is in no page): the cache makes no lookup and counts
``decode.prefix.skipped``.
"""
from __future__ import annotations

import numpy as np

from ...gluon.block import HybridBlock
from ...ndarray import NDArray, invoke_fn
from ...telemetry import bus as _tel
from .latent_moe import (_dot, _einsum, _rms, _swiglu, moe_rows_of,
                         record_moe_rows)
from .model import commit_destinations, sample_math

__all__ = ["WindowMoELM"]

GLOBAL, WINDOW = 0, 1


def _softmax_with_sink(s, sink):
    """Softmax of ``s (B, g, r, Q, L)`` over ``L`` with ``sink (g, r)`` (or
    None) as one more column of the denominator that carries no value: ``p
    = exp(s - m) / (exp(sink - m) + sum exp(s - m))``, ``m`` the largest of
    the row's scores and the sink."""
    import jax.numpy as jnp
    m = s.max(-1, keepdims=True)
    if sink is None:
        e = jnp.exp(s - m)
        return e / e.sum(-1, keepdims=True)
    sink = sink[None, :, :, None, None]
    m = jnp.maximum(m, sink)
    e = jnp.exp(s - m)
    return e / (e.sum(-1, keepdims=True) + jnp.exp(sink - m))


class WindowMoELM(HybridBlock):
    """Decoder-only transformer of sliding-window and global grouped-query
    layers with routed experts; see the module docstring.  ``forward(tokens
    (B, S), lengths (B,))`` returns ``(last_logits (B, vocab) float32,
    k_rows (global layers, B, S, kv_heads * head_dim), v_rows (global
    layers, B, S, kv_heads * v_head_dim), ring_k (window layers, B, window,
    swa kv_heads * head_dim), ring_v (...  * v_head_dim))`` for the
    runtime's commit program.

    ``layer_pattern`` and ``moe_layer_freq`` give each layer's attention
    kind and MLP kind and need at least one window and one global layer.
    ``held_experts`` are the global ids of the routed experts held here
    (default: all); the router is always ``n_routed_experts`` wide.
    ``vocab_size`` is the slice of the vocabulary held here."""

    def __init__(self, vocab_size=512, hidden_size=64,
                 layer_pattern=(0, 1, 1, 0, 1), moe_layer_freq=(0, 1, 1, 1, 1),
                 num_attention_heads=4, num_key_value_heads=1,
                 swa_num_key_value_heads=2, head_dim=24, v_head_dim=16,
                 partial_rotary_factor=0.334, rope_theta=1e7,
                 swa_rope_theta=1e4, sliding_window=8,
                 attention_value_scale=0.707, intermediate_size=128,
                 moe_intermediate_size=32, n_routed_experts=16,
                 held_experts=None, num_experts_per_tok=4, n_group=1,
                 topk_group=1, routed_scaling_factor=None, norm_eps=1e-5,
                 max_length=128, dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self.layer_pattern = tuple(int(k) for k in layer_pattern)
        self.moe_layer_freq = tuple(int(k) for k in moe_layer_freq)
        kinds = set(self.layer_pattern)
        if kinds != {GLOBAL, WINDOW} or \
                set(self.moe_layer_freq) - {0, 1} or \
                len(self.moe_layer_freq) != len(self.layer_pattern):
            raise ValueError(
                f"layer_pattern={self.layer_pattern} (0 global, 1 window) "
                f"needs a layer of each kind, and moe_layer_freq="
                f"{self.moe_layer_freq} (0 dense, 1 experts) an entry a "
                f"layer")
        self.vocab_size, self.units = int(vocab_size), int(hidden_size)
        self.num_layers = len(self.layer_pattern)
        self.global_layers = tuple(
            i for i, k in enumerate(self.layer_pattern) if k == GLOBAL)
        self.window_layers = tuple(
            i for i, k in enumerate(self.layer_pattern) if k == WINDOW)
        self.moe_layers = tuple(
            i for i, k in enumerate(self.moe_layer_freq) if k)
        # a layer's index among its own kind: its layer of the page pools /
        # of the ring pools
        self._nth = {i: n for ls in (self.global_layers, self.window_layers)
                     for n, i in enumerate(ls)}
        self.q_heads = int(num_attention_heads)
        self.kv_heads = {GLOBAL: int(num_key_value_heads),
                         WINDOW: int(swa_num_key_value_heads)}
        for g in self.kv_heads.values():
            if self.q_heads % g:
                raise ValueError(
                    f"num_attention_heads={self.q_heads} is not divisible "
                    f"by {g} key/value heads")
        self.head_dim, self.v_dim = int(head_dim), int(v_head_dim)
        self.rot_dim = int(self.head_dim * float(partial_rotary_factor))
        if self.rot_dim % 2:
            raise ValueError(
                f"partial_rotary_factor={partial_rotary_factor} of head_dim="
                f"{self.head_dim} is {self.rot_dim} rotary dimensions: "
                f"rotate-half pairing needs an even number")
        j = np.arange(0, self.rot_dim, 2, dtype="float64") / self.rot_dim
        self._inv_freq = {GLOBAL: (float(rope_theta) ** -j).astype("float32"),
                          WINDOW: (float(swa_rope_theta) ** -j
                                   ).astype("float32")}
        self.window = int(sliding_window)
        self.v_scale = float(attention_value_scale)
        self.n_routed = int(n_routed_experts)
        self.held = tuple(range(self.n_routed)) if held_experts is None \
            else tuple(int(e) for e in held_experts)
        if not self.held or len(set(self.held)) != len(self.held) or \
                not all(0 <= e < self.n_routed for e in self.held):
            raise ValueError(
                f"held_experts={self.held} must be distinct ids in "
                f"[0, {self.n_routed})")
        self.top_k = int(num_experts_per_tok)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.routed_scale = 1.0 if routed_scaling_factor is None \
            else float(routed_scaling_factor)
        self.eps = float(norm_eps)
        self.max_length = int(max_length)
        self.dtype = str(dtype)
        u, wd, G, H = self.units, self.dtype, len(self.held), self.q_heads

        def reg(name, shape, init="normal", dtype=wd):
            setattr(self, name, self.params.get(name, shape=shape, init=init,
                                                dtype=dtype))

        reg("embed", (self.vocab_size, u))
        reg("head", (u, self.vocab_size))
        reg("norm_f", (u,), "ones", "float32")
        for i, kind in enumerate(self.layer_pattern):
            p, g = f"l{i}_", self.kv_heads[kind]
            reg(p + "norm_attn", (u,), "ones", "float32")
            reg(p + "wq", (u, H * self.head_dim))
            reg(p + "wk", (u, g * self.head_dim))
            reg(p + "wv", (u, g * self.v_dim))
            reg(p + "wo", (H * self.v_dim, u))
            if kind == WINDOW:
                # a host initialiser gives every head a sink of 0: one more
                # key of score 0 and no value
                reg(p + "sink", (H,), "zeros", "float32")
            reg(p + "norm_ffn", (u,), "ones", "float32")
            if self.moe_layer_freq[i]:
                f = int(moe_intermediate_size)
                # float32 router scores at the highest precision, so that
                # the choice of experts follows the reference's
                reg(p + "router", (u, self.n_routed), dtype="float32")
                reg(p + "router_bias", (self.n_routed,), "zeros", "float32")
                reg(p + "exp_wg", (G, u, f))
                reg(p + "exp_wu", (G, u, f))
                reg(p + "exp_wd", (G, f, u))
            else:
                f = int(intermediate_size)
                reg(p + "wg", (u, f))
                reg(p + "wu", (u, f))
                reg(p + "wd", (f, u))
        self._param_order = sorted(self._reg_params)

    # ------------------------------------------------- what the runtime reads
    #: one prompt a prefill call: a prompt of a window or more already fills
    #: the MXU's rows, and the scores of a block are per row
    max_prefill_batch = 1

    def _widths(self, kind):
        """``(key row, value row)`` widths of one token in a layer of
        ``kind``."""
        g = self.kv_heads[kind]
        return g * self.head_dim, g * self.v_dim

    def cache_layout(self):
        """Two kinds of attention state under one allocator.  Paged: K and
        V pools with one layer for each GLOBAL layer (rows of ``kv_heads *
        head_dim`` and ``kv_heads * v_head_dim`` values).  A slot: for each
        WINDOW layer a ring of ``window`` tokens' keys and values, bounded
        whatever the context.  Not quantizable, not sharded."""
        kg, vg = self._widths(GLOBAL)
        kw, vw = self._widths(WINDOW)
        return {"layers": len(self.global_layers),
                "pools": (("k", kg, self.dtype), ("v", vg, self.dtype)),
                "quantizable": False, "shard_heads": None,
                "max_length": self.max_length,
                "state": {"layers": len(self.window_layers),
                          "arrays": (("ring_k", (self.window, kw),
                                      self.dtype),
                                     ("ring_v", (self.window, vw),
                                      self.dtype))}}

    def prefill_state(self, b, s):
        """Shapes and dtypes of what :meth:`prefill_math` emits behind the
        logits: the global layers' K rows and V rows, the window layers'
        rings of keys and of values."""
        Lg, Lw = len(self.global_layers), len(self.window_layers)
        kg, vg = self._widths(GLOBAL)
        kw, vw = self._widths(WINDOW)
        return (((Lg, b, s, kg), self.dtype), ((Lg, b, s, vg), self.dtype),
                ((Lw, b, self.window, kw), self.dtype),
                ((Lw, b, self.window, vw), self.dtype))

    def _params_dict(self, leaves):
        return dict(zip(self._param_order, leaves))

    def param_leaves(self):
        return [self._reg_params[n].data()._data for n in self._param_order]

    # ------------------------------------------------------------ pure math
    def _rope(self, x, positions, kind):
        """Rotate the first ``rot_dim`` dimensions of the last axis of ``x
        (..., heads, head_dim)`` at ``positions (...)`` with the layer
        kind's base, pairing dimension ``j`` with ``j + rot_dim / 2``
        (rotate-half); the rest pass."""
        import jax.numpy as jnp
        half = self.rot_dim // 2
        ang = positions[..., None, None].astype(jnp.float32) \
            * jnp.asarray(self._inv_freq[kind])
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:self.rot_dim]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                x[..., self.rot_dim:]], axis=-1)

    def _qkv(self, p, i, a, positions):
        """``(q (..., g, r, head_dim) float32, k (..., g * head_dim), v
        (..., g * v_head_dim) in the cache dtype)`` of ``a (..., U)`` at
        ``positions (...)``: rotated, the values scaled; query head ``j``
        sits at ``[j // r, j % r]``, under the K/V head it reads."""
        pre, kind = f"l{i}_", self.layer_pattern[i]
        g = self.kv_heads[kind]
        lead = a.shape[:-1]
        q = self._rope(_dot(a, p[pre + "wq"]).reshape(
            lead + (self.q_heads, self.head_dim)), positions, kind)
        k = self._rope(_dot(a, p[pre + "wk"]).reshape(
            lead + (g, self.head_dim)), positions, kind)
        v = _dot(a, p[pre + "wv"]) * self.v_scale
        return (q.reshape(lead + (g, self.q_heads // g, self.head_dim)),
                k.reshape(lead + (-1,)).astype(self.dtype),
                v.astype(self.dtype))

    def _sink(self, p, i):
        """The window layer's sink bias as ``(g, r)``, or None for a global
        layer."""
        if self.layer_pattern[i] == GLOBAL:
            return None
        g = self.kv_heads[WINDOW]
        return p[f"l{i}_sink"].reshape(g, self.q_heads // g)

    def attend(self, p, i, q, k, v, mask):
        """Attention of ``q (B, Q, g, r, head_dim)`` over ``k (B, L, g *
        head_dim)``, ``v (B, L, g * v_head_dim)`` (stored precision) where
        ``mask (B, Q, L)`` allows, the layer's sink in the denominator.
        Returns the heads' outputs ``(B, Q, heads * v_head_dim)`` float32,
        before the output projection."""
        import jax.numpy as jnp
        B, L, _ = k.shape
        g, dt = q.shape[2], self.dtype
        k = k.reshape(B, L, g, self.head_dim)
        v = v.reshape(B, L, g, self.v_dim)
        s = _einsum("bqgrd,blgd->bgrql", q, k, dt) * self.head_dim ** -0.5
        s = jnp.where(mask[:, None, None], s, -1e30)
        pr = _softmax_with_sink(s, self._sink(p, i))
        if self.layer_pattern[i] == WINDOW:
            # the values handed over tokens-minor, as a copy of the few a
            # ring holds: read straight from the ring, the one-row step's
            # slice of the donated pool is no copy, the chip's compiler
            # then keeps the WHOLE pool tokens-minor for this product, and
            # the program begins and ends with a copy of it (sandbox
            # compile, PR 32; tests/test_chip_compile.py holds it)
            o = _einsum("bgrql,bgdl->bqgrd", pr, v.transpose(0, 2, 3, 1), dt)
        else:
            o = _einsum("bgrql,blgd->bqgrd", pr, v, dt)
        return o.reshape(o.shape[:2] + (-1,))

    def attend_prompt(self, p, i, q, k, v):
        """Causal attention of a whole padded prompt, ``q (B, S, g, r,
        head_dim)`` over its own ``k``, ``v (B, S, width)``, by query blocks
        of ``window`` tokens and never a ``(heads, S, S)`` array: a window
        layer's block reads the block before it and its own (the band), a
        global layer's blocks are mapped one after another over all the
        keys.  Returns ``(B, S, heads * v_head_dim)`` float32."""
        import jax
        import jax.numpy as jnp
        B, S = q.shape[:2]
        W = self.window
        nb = -(-S // W)
        pad = nb * W - S
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
            k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (k, v))
        a = jnp.arange(W)
        if self.layer_pattern[i] == WINDOW:
            # block n's keys: block n - 1 (zeros before the first), then
            # block n; key c of the 2W stands at position (n - 1) W + c
            def banded(x):
                x = x.reshape(B, nb, W, -1)
                before = jnp.pad(x, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
                return jnp.concatenate([before, x], axis=2).reshape(
                    B * nb, 2 * W, -1)

            c = jnp.arange(2 * W)
            band = (c[None, :] > a[:, None]) & (c[None, :] <= a[:, None] + W)
            real = (jnp.arange(nb)[:, None] > 0) | (c[None, :] >= W)
            mask = jnp.tile(band[None] & real[:, None, :], (B, 1, 1))
            o = self.attend(p, i, q.reshape((B * nb, W) + q.shape[2:]),
                            banded(k), banded(v), mask)
            return o.reshape(B, nb * W, -1)[:, :S]

        keys = jnp.arange(nb * W)

        def block(args):
            qb, n = args
            mask = keys[None, :] <= (n * W + a)[:, None]
            return self.attend(p, i, qb, k, v,
                               jnp.broadcast_to(mask, (B,) + mask.shape))

        qs = q.reshape((B, nb, W) + q.shape[2:]).swapaxes(0, 1)
        o = jax.lax.map(block, (qs, jnp.arange(nb)))
        return o.swapaxes(0, 1).reshape(B, nb * W, -1)[:, :S]

    def _ring_of(self, rows, lengths):
        """A window layer's ring as of ``lengths``: entry ``e`` holds the
        row of the last position ``<= length - 1`` that is ``e`` modulo the
        window, zeros where the prompt has none.  ``rows (B, S, width)`` ->
        ``(B, window, width)``."""
        import jax.numpy as jnp
        W = self.window
        last = (lengths - 1)[:, None]
        pos = last - (last - jnp.arange(W)[None, :]) % W
        ring = jnp.take_along_axis(
            rows, jnp.clip(pos, 0, rows.shape[1] - 1)[:, :, None], axis=1)
        return jnp.where((pos >= 0)[:, :, None], ring, 0)

    def _mlp(self, p, i, h, valid, counts):
        """``h + mlp(RMSNorm(h))`` over flat rows ``h (T, U)``."""
        import jax
        from ...parallel.moe import routed_expert_share
        pre = f"l{i}_"
        m = _rms(h, p[pre + "norm_ffn"], self.eps)
        if not self.moe_layer_freq[i]:
            with jax.named_scope("ffn.dense"):
                return h + _swiglu(m, p[pre + "wg"], p[pre + "wu"],
                                   p[pre + "wd"])
        y, rows, n_assign = routed_expert_share(
            m, p[pre + "router"], p[pre + "exp_wg"], p[pre + "exp_wu"],
            p[pre + "exp_wd"], self.held, top_k=self.top_k,
            n_group=self.n_group, topk_group=self.topk_group,
            scale=self.routed_scale, valid=valid,
            select_bias=p[pre + "router_bias"])
        counts.append((rows, n_assign))
        return h + y

    def _scope_of(self, i):
        return "attn.window" if self.layer_pattern[i] == WINDOW \
            else "attn.global"

    def prefill_math(self, p, tokens, lengths):
        """Pure prefill: ``(last_logits, k_rows, v_rows, ring_k, ring_v)``
        — see the class docstring.  Padded positions are routed to no
        expert and are in no ring."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        h = p["embed"][tokens].astype(jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        valid = (pos < lengths[:, None]).reshape(-1)
        ks, vs, rks, rvs, counts = [], [], [], [], []
        for i, kind in enumerate(self.layer_pattern):
            a = _rms(h, p[f"l{i}_norm_attn"], self.eps)
            with jax.named_scope(self._scope_of(i)):
                q, k, v = self._qkv(p, i, a, pos)
                o = _dot(self.attend_prompt(p, i, q, k, v), p[f"l{i}_wo"])
                if kind == WINDOW:
                    rks.append(self._ring_of(k, lengths))
                    rvs.append(self._ring_of(v, lengths))
                else:
                    ks.append(k)
                    vs.append(v)
            h = self._mlp(p, i, (h + o).reshape(B * S, -1), valid,
                          counts).reshape(B, S, -1)
        last = _rms(h[jnp.arange(B), lengths - 1], p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(last, p["head"])
        return (logits, jnp.stack(ks), jnp.stack(vs), jnp.stack(rks),
                jnp.stack(rvs))

    def step_program(self, p, tokens, positions, tables, pools, pages):
        """Pure fused decode step, one token a row.  ``tables`` ends with
        each row's state slot (``pages.addresses``).  A window layer writes
        the token's K/V into entry ``position mod window`` of the slot's
        ring and attends over the ring (an entry holds a position of the
        last ``window``, or one that this sequence never wrote: masked); a
        global layer writes into the row's page and attends over the row's
        paged context through ``pages.attend``.  Padded rows (page table
        all trash) use the trash slot and are routed to no expert.  Returns
        ``(logits (B, vocab), pools, (moe_rows (expert layers, held + 1)
        int32, live rows (1,) int32))``."""
        import jax
        import jax.numpy as jnp
        ptab, srow = pages.addresses(tables)
        page_size, W = pages.page_size, self.window
        h = p["embed"][tokens].astype(jnp.float32)
        wp = jnp.take_along_axis(ptab, (positions // page_size)[:, None],
                                 axis=1)[:, 0]
        woff = positions % page_size
        # ring entry e holds position p - (p - e) mod W: one of the last W
        # where that is not negative
        went = positions % W
        held = positions[:, None] - (positions[:, None]
                                     - jnp.arange(W)[None, :]) % W
        ringed = (held >= 0)[:, None]
        valid = ptab[:, 0] != 0
        counts = []
        for i, kind in enumerate(self.layer_pattern):
            a = _rms(h, p[f"l{i}_norm_attn"], self.eps)
            n = self._nth[i]
            with jax.named_scope(self._scope_of(i)):
                q, k, v = self._qkv(p, i, a, positions)
                if kind == WINDOW:
                    pools = pages.state.write_at(pools, n, srow, went, (k, v))
                    ck, cv = pages.state.read(pools, n, srow)
                    o = self.attend(p, i, q[:, None], ck, cv, ringed)[:, 0]
                else:
                    pools = pages.write(pools, n, wp, woff, (k, v))
                    o = pages.attend(
                        pools, n, ptab, positions, q,
                        lambda ck, cv, paged: self.attend(
                            p, i, q[:, None], ck, cv, paged)[:, 0])
                o = _dot(o, p[f"l{i}_wo"])
            h = self._mlp(p, i, h + o, valid, counts)
        hf = _rms(h, p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(hf, p["head"])
        return logits, pools, (moe_rows_of(counts, len(self.held)),
                               valid.sum().astype(jnp.int32)[None])

    def commit_program(self, state, lengths, tables, pools, pages):
        """Store a prefill's ``(k_rows, v_rows, ring_k, ring_v)``: the
        global layers' rows in the pages ``tables`` names, and each window
        layer's ring as the WHOLE ring of the row's slot — whatever the
        slot's last owner left is overwritten here, before any step reads
        it."""
        k_rows, v_rows, ring_k, ring_v = state
        ptab, srow = pages.addresses(tables)
        dest_page, dest_off = commit_destinations(
            k_rows.shape[2], lengths, ptab, pages.page_size)
        for n in range(len(self.global_layers)):
            pools = pages.write(pools, n, dest_page, dest_off,
                                (k_rows[n], v_rows[n]))
        for n in range(len(self.window_layers)):
            pools = pages.state.write(pools, n, srow, (ring_k[n], ring_v[n]))
        return pools

    sample_math = staticmethod(sample_math)

    @property
    def ring_bytes_per_row(self):
        """Device bytes one sequence's rings cost (every window layer)."""
        import jax.numpy as jnp
        return len(self.window_layers) * self.window \
            * sum(self._widths(WINDOW)) * jnp.dtype(self.dtype).itemsize

    def record_step_extras(self, extras, model):
        """Telemetry from one step's counts (the program's vector of them,
        flat): the ``decode.moe.*`` counters of the shared expert layer,
        ``decode.window.layer_steps`` / ``decode.window.ring_rows`` (window
        layers run, and live rows' rings they wrote and read), and the
        gauges ``decode.window.live_rows`` / ``decode.window.live_bytes``
        (the rings that a live sequence holds, and their bytes)."""
        extras = np.asarray(extras)
        live = int(extras[-1])
        _tel.count("decode.window.layer_steps", len(self.window_layers),
                   model=model)
        _tel.count("decode.window.ring_rows", live * len(self.window_layers),
                   model=model)
        _tel.gauge("decode.window.live_rows", live)
        _tel.gauge("decode.window.live_bytes", live * self.ring_bytes_per_row)
        record_moe_rows(extras[:-1].reshape(-1, len(self.held) + 1), model)

    # ------------------------------------------------------- gluon frontend
    def hybrid_forward(self, F, tokens, lengths, **params):
        if not isinstance(tokens, NDArray) and not hasattr(tokens, "_data"):
            raise NotImplementedError(
                "WindowMoELM has no symbolic frontend (export is not "
                "supported); the decode runtime compiles it through "
                "compile_grid / the CachedOp path instead")
        leaves = [params[n] for n in self._param_order]

        def pure(tok, ln_, *leaf_vals):
            return self.prefill_math(self._params_dict(leaf_vals), tok, ln_)

        return tuple(invoke_fn(pure, [tokens, lengths] + leaves,
                               op_name="window_moe_prefill"))
