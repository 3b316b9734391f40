"""Hybrid state-space / attention / routed-expert decode model (the
``nemotron_h`` family's block, as Nemotron-3-Nano publishes it) — a third
block beside :class:`CausalLM` and :class:`LatentMoELM` behind the same
runtime, scheduler and cache.

Every layer is ``h <- h + mixer(RMSNorm(h))`` with ONE mixer, named by its
letter in ``pattern`` (the config's ``hybrid_override_pattern``):

- ``M`` — a **Mamba-2 mixer** (``mxnet_tpu.ops.ssm``): one input projection
  ``[z | xBC | dt]``, a depthwise causal convolution over ``xBC``, the
  selective state-space recurrence per head with ``B`` / ``C`` shared by a
  group of heads, a gated group RMS norm, an output projection.  Its state
  is **per sequence, not per token**: the recurrent state ``(heads,
  head_dim, state)`` in float32 and the convolution's last ``conv_kernel -
  1`` inputs, kept in the cache's *state pools* a slot
  (:meth:`cache_layout`'s ``state`` section; ``kv_format.SlotState``).
  Prefill runs the **chunked** scan and hands over the state and the tail
  **as of each row's true length** (padding behind it has ``dt`` 0); the
  step advances each live row's state where it lies in the donated pool,
  in one kernel a layer (``ops.pallas_kernels.ssm_step_slots``; where the
  program is lowered for the CPU, its definition ``ops.ssm.ssm_step``
  between the slot's ``read`` and ``write``).
- ``*`` — **grouped-query attention**: ``num_attention_heads`` queries over
  ``num_key_value_heads`` keys and values (query head ``j`` reads KV head
  ``j // (heads / kv heads)``), no biases and no rotary (the state-space
  layers carry position).  Only these layers page: the K/V pools have one
  layer for each ``*``.
- ``E`` — **routed + shared experts**, un-gated ``relu(x W_up)^2 W_down``:
  :func:`routed_relu2_share` over the experts ``held_experts`` (one chip's
  share; the routing is ``parallel.moe``'s, the products a dense chain an
  expert hit), plus one shared expert of the same form, unweighted.

Precision and contract as :class:`LatentMoELM`: weights, K/V rows and the
convolution tail are ``dtype`` (bfloat16 as served; the input to the
convolution is rounded to it in prefill and step alike, so both see what
the tail stores); products are in that dtype with float32 accumulation;
the residual stream, norms, softmax, router scores, ``dt``, the decay, the
recurrent state and the logits are float32.  Held to the plain reference
(``perf/reference/nemotron_h.py``) within the tolerances
``tests/test_hybrid_moe_lm.py`` writes down, not to bitwise row stability.

What the block refuses, each with a sentence: a drafter (verifying a draft
would need the recurrent state rolled back to the accepted prefix: there is
no verify program), quantized pools, and a mesh.  Prefix sharing is a no-op
for it (the state at a prefix boundary is in no page): the cache makes no
lookup and counts ``decode.prefix.skipped``.
"""
from __future__ import annotations

import math

import numpy as np

from ...gluon.block import HybridBlock
from ...ndarray import NDArray, invoke_fn
from ...telemetry import bus as _tel
from .latent_moe import (_dot, _einsum, _rms, moe_rows_of,
                         record_moe_rows)
from .model import commit_destinations, sample_math

__all__ = ["HybridSSMMoELM"]


def _relu2(x, wu, wd):
    import jax
    import jax.numpy as jnp
    return _dot(jnp.square(jax.nn.relu(_dot(x, wu))), wd)


def routed_relu2_share(x, router_w, w_up, w_down, held, *, top_k, n_group=1,
                       topk_group=1, scale=1.0, valid=None):
    """This chip's part of ``sum_k w_k relu(x W_up,k)^2 W_down,k`` for ``x
    (T, D)`` float32 — ``parallel.moe.routed_expert_share``'s contract and
    answer (``(y, rows, assignments)``) for un-gated experts, with the
    shared layer's routing (``route_to_held``) and products of its own: ONE
    DENSE CHAIN AN EXPERT over all ``T`` rows, weighted by the expert's
    weight for each row (0 where the row did not choose it), under a
    conditional that skips an expert no row chose, so that its weights are
    not read.  A step or a one-prompt prefill puts few rows through an
    expert, and at this expert's size the chip's grouped product does not
    stream: it took 27 ms of a 42 ms step at 14 rows where these chains take
    5 (``PERF.md``, PR 30)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ...parallel.moe import route_to_held, rows_received
    G = len(held)
    with jax.named_scope("moe.route"):
        local, weights, n_assign = route_to_held(
            x, router_w, held, top_k=top_k, n_group=n_group,
            topk_group=topk_group, scale=scale, valid=valid)
        rows = rows_received(local.reshape(-1), G)
        # (T, G): each held expert's weight for each row
        coef = ((local[:, :, None] == jnp.arange(G, dtype=jnp.int32))
                * weights[:, :, None]).sum(1)

    def one(g):
        hidden = jnp.square(jax.nn.relu(_dot(x, w_up[g])))
        return _dot(hidden, w_down[g]) * coef[:, g:g + 1]

    with jax.named_scope("moe.experts"):
        y = jnp.zeros((x.shape[0], w_down.shape[-1]), jnp.float32)
        for g in range(G):
            y = y + lax.cond(rows[g] > 0, lambda g=g: one(g),
                             lambda: jnp.zeros_like(y))
    return y, rows, n_assign


def gqa_heads(q, k, v, mask, kv_heads, head_dim, dtype):
    """Grouped-query attention of ``q (B, Q, g, r, D)`` float32 over ``k``,
    ``v (B, L, g * D)`` (stored precision) where ``mask (B, Q, L)`` allows,
    at scale ``D^-0.5``, products in ``dtype`` and the softmax float32: the
    heads' outputs side by side, ``(B, Q, g * r * D)`` float32."""
    import jax
    import jax.numpy as jnp
    B, L, _ = k.shape
    k = k.reshape(B, L, kv_heads, head_dim)
    v = v.reshape(B, L, kv_heads, head_dim)
    s = _einsum("bqgrd,blgd->bgrql", q, k, dtype) * head_dim ** -0.5
    s = jnp.where(mask[:, None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = _einsum("bgrql,blgd->bqgrd", pr, v, dtype)
    return o.reshape(o.shape[:2] + (-1,))


class HybridSSMMoELM(HybridBlock):
    """Decoder-only hybrid of Mamba-2, grouped-query attention and routed +
    shared experts; see the module docstring.  ``forward(tokens (B, S),
    lengths (B,))`` returns ``(last_logits (B, vocab) float32, k_rows,
    v_rows (attention layers, B, S, kv_heads * head_dim), ssm (mamba
    layers, B, heads, head_dim, state) float32, conv (mamba layers, B) +
    the stored tail's shape)`` for the runtime's commit program.

    ``pattern`` needs at least one ``M`` and one ``*``.  ``held_experts``
    are the global ids of the routed experts held here (default: all); the
    router is always ``n_routed_experts`` wide.  ``vocab_size`` is the
    slice of the vocabulary held here."""

    def __init__(self, vocab_size=512, hidden_size=64, pattern="ME*ME",
                 mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
                 n_groups=2, conv_kernel=4, chunk_size=16,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 moe_intermediate_size=32,
                 moe_shared_expert_intermediate_size=64,
                 n_routed_experts=16, held_experts=None,
                 num_experts_per_tok=4, n_group=1, topk_group=1,
                 routed_scaling_factor=2.5, norm_eps=1e-5, max_length=128,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self.pattern = str(pattern)
        if set(self.pattern) - set("ME*") or "M" not in self.pattern \
                or "*" not in self.pattern:
            raise ValueError(
                f"pattern={self.pattern!r}: a string of 'M' (Mamba-2), 'E' "
                f"(experts) and '*' (attention) with at least one 'M' and "
                f"one '*'")
        self.vocab_size, self.units = int(vocab_size), int(hidden_size)
        self.num_layers = len(self.pattern)
        kinds = {k: [i for i, c in enumerate(self.pattern) if c == k]
                 for k in "ME*"}
        self.mamba_layers, self.moe_layers, self.attn_layers = \
            tuple(kinds["M"]), tuple(kinds["E"]), tuple(kinds["*"])
        # a layer's index among its own kind: its row of the state pools /
        # of the K/V pools
        self._nth = {i: n for ls in kinds.values() for n, i in enumerate(ls)}
        self.m_heads, self.m_dim = int(mamba_num_heads), int(mamba_head_dim)
        self.d_inner = self.m_heads * self.m_dim
        self.n_state, self.n_groups = int(ssm_state_size), int(n_groups)
        if self.m_heads % self.n_groups:
            raise ValueError(f"mamba_num_heads={self.m_heads} is not "
                             f"divisible by n_groups={self.n_groups}")
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk_size)
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.n_state
        self.q_heads, self.kv_heads = int(num_attention_heads), \
            int(num_key_value_heads)
        if self.q_heads % self.kv_heads:
            raise ValueError(
                f"num_attention_heads={self.q_heads} is not divisible by "
                f"num_key_value_heads={self.kv_heads}")
        self.head_dim = int(head_dim)
        self.kv_width = self.kv_heads * self.head_dim
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
        self.routed_scale = float(routed_scaling_factor)
        self.eps = float(norm_eps)
        self.max_length = int(max_length)
        self.dtype = str(dtype)
        u, wd, G = self.units, self.dtype, len(self.held)
        fs = int(moe_shared_expert_intermediate_size)
        # a held expert's hidden axis fills whole 128-lane tiles in storage:
        # left ragged (1856 = 14.5 tiles), the chip stores ``(G, U, F)``
        # with U minor-most and every step first copies all the held
        # experts' up-projections into the layout its products read (once
        # a layer under grouped products, once an EXPERT under products by
        # expert: sandbox compiles, PR 30).  The added columns of W_up and
        # rows of W_down are zeros (:meth:`stored`) and add nothing:
        # relu(0)^2 = 0
        self.expert_width = int(moe_intermediate_size)
        f = -(-self.expert_width // 128) * 128

        def reg(name, shape, init="normal", dtype=wd):
            setattr(self, name, self.params.get(name, shape=shape, init=init,
                                                dtype=dtype))

        from ... import initializer as _init
        reg("embed", (self.vocab_size, u))
        reg("head", (u, self.vocab_size))
        reg("norm_f", (u,), "ones", "float32")
        for i, kind in enumerate(self.pattern):
            p = f"l{i}_"
            reg(p + "norm", (u,), "ones", "float32")
            if kind == "M":
                reg(p + "w_in", (u, 2 * self.d_inner
                                 + 2 * self.n_groups * self.n_state
                                 + self.m_heads))
                reg(p + "conv_w", (self.conv_dim, self.conv_kernel),
                    dtype="float32")
                reg(p + "conv_b", (self.conv_dim,), "zeros", "float32")
                # the family draws these a head; a host initialiser gives
                # every head the middle of its ranges (dt 0.01, A -4)
                reg(p + "dt_bias", (self.m_heads,),
                    _init.Constant(math.log(math.expm1(0.01))), "float32")
                reg(p + "A_log", (self.m_heads,),
                    _init.Constant(math.log(4.0)), "float32")
                reg(p + "D", (self.m_heads,), "ones", "float32")
                reg(p + "norm_gate", (self.d_inner,), "ones", "float32")
                reg(p + "w_out", (self.d_inner, u))
            elif kind == "*":
                reg(p + "wq", (u, self.q_heads * self.head_dim))
                reg(p + "wk", (u, self.kv_width))
                reg(p + "wv", (u, self.kv_width))
                reg(p + "wo", (self.q_heads * self.head_dim, u))
            else:
                # float32 router scores at the highest precision, so that
                # the choice of experts follows the reference's
                reg(p + "router", (u, self.n_routed), dtype="float32")
                reg(p + "exp_wu", (G, u, f))
                reg(p + "exp_wd", (G, f, u))
                reg(p + "sh_wu", (u, fs))
                reg(p + "sh_wd", (fs, u))
        self._param_order = sorted(self._reg_params)

    # ------------------------------------------------- what the runtime reads
    #: one prompt a prefill call: the within-chunk decay matrices and the
    #: attention scores are per row, and a prompt of a chunk or more already
    #: fills the MXU's rows
    max_prefill_batch = 1

    def cache_layout(self):
        """Two kinds of state.  Paged: K and V pools with one layer for each
        attention layer, a row of ``kv_heads * head_dim`` values in the
        block's dtype.  A slot: for each Mamba layer the recurrent state
        (float32) and the convolution's tail, oldest input first.
        Not quantizable, not sharded."""
        return {"layers": len(self.attn_layers),
                "pools": (("k", self.kv_width, self.dtype),
                          ("v", self.kv_width, self.dtype)),
                "quantizable": False, "shard_heads": None,
                "max_length": self.max_length,
                "state": {"layers": len(self.mamba_layers),
                          "arrays": (("ssm", (self.m_heads, self.m_dim,
                                              self.n_state), "float32"),
                                     ("conv", self._tail_shape,
                                      self.dtype))}}

    @property
    def _tail_shape(self):
        """A slot's convolution tail as stored: ``(K - 1) * conv_dim``
        values, oldest input first, as rows of 128 lanes where they divide
        (a pool whose minor dimensions are whole tiles is updated where it
        lies, as the recurrent state's is; a flat row a slot puts the SLOTS
        on the tiles' second axis, and the chip then keeps the pool in
        another order and copies it about)."""
        tail = (self.conv_kernel - 1) * self.conv_dim
        return (tail // 128, 128) if tail % 128 == 0 else (tail,)

    def prefill_state(self, b, s):
        """Shapes and dtypes of what :meth:`prefill_math` emits behind the
        logits: K rows, V rows, recurrent states, convolution tails."""
        La, Lm = len(self.attn_layers), len(self.mamba_layers)
        kv = ((La, b, s, self.kv_width), self.dtype)
        return (kv, kv,
                ((Lm, b, self.m_heads, self.m_dim, self.n_state), "float32"),
                ((Lm, b) + self._tail_shape, self.dtype))

    def stored(self, name, array):
        """``array``, a checkpoint's tensor for parameter ``name``, as the
        block stores it: the routed experts' hidden axis (``exp_wu``'s
        last, ``exp_wd``'s middle) zero-padded to whole lane tiles; every
        other tensor as it is."""
        import jax.numpy as jnp
        axis = {"exp_wu": 2, "exp_wd": 1}.get(name.split("_", 1)[-1])
        want = self._reg_params[name].shape
        if axis is None or array.shape[axis] == want[axis]:
            return array
        pad = [(0, 0)] * 3
        pad[axis] = (0, want[axis] - array.shape[axis])
        return jnp.pad(array, pad)

    def _params_dict(self, leaves):
        return dict(zip(self._param_order, leaves))

    def param_leaves(self):
        return [self._reg_params[n].data()._data for n in self._param_order]

    # ------------------------------------------------------------ pure math
    def _mamba_inputs(self, p, i, a):
        """``(z, xBC in the stored dtype, dt before its bias)`` of ``a (...,
        U)``: the one input projection, split."""
        di, cd = self.d_inner, self.conv_dim
        zxd = _dot(a, p[f"l{i}_w_in"])
        return zxd[..., :di], zxd[..., di:di + cd].astype(self.dtype), \
            zxd[..., di + cd:]

    def _mamba_split(self, xc):
        """The convolved ``xBC (..., conv_dim)`` as ``(x (..., H, P), B, C
        (..., G, N))``."""
        di, gn = self.d_inner, self.n_groups * self.n_state
        lead = xc.shape[:-1]
        return (xc[..., :di].reshape(lead + (self.m_heads, self.m_dim)),
                xc[..., di:di + gn].reshape(lead + (self.n_groups,
                                                    self.n_state)),
                xc[..., di + gn:].reshape(lead + (self.n_groups,
                                                  self.n_state)))

    def _mamba_out(self, p, i, y, z):
        from ...ops.ssm import gated_group_norm
        pre = f"l{i}_"
        v = gated_group_norm(y.reshape(z.shape), z, p[pre + "norm_gate"],
                             self.n_groups, self.eps)
        return _dot(v, p[pre + "w_out"])

    def mamba_prefill(self, p, i, a, valid, lengths):
        """The Mamba mixer over whole sequences ``a (B, S, U)``: returns
        ``(output (B, S, U), state (B, H, P, N) float32, tail (B, (K - 1) *
        conv_dim))``, the state and the tail as of ``lengths``."""
        import jax
        import jax.numpy as jnp
        from ...ops import ssm
        pre = f"l{i}_"
        with jax.named_scope("ssm.mix"):
            z, xbc, dt = self._mamba_inputs(p, i, a)
        with jax.named_scope("ssm.conv"):
            xc = ssm.causal_conv(xbc, p[pre + "conv_w"], p[pre + "conv_b"])
            tail = ssm.conv_tail(xbc, lengths, self.conv_kernel)
        with jax.named_scope("ssm.mix"):
            x, Bm, Cm = self._mamba_split(xc)
            # padding neither decays the state nor feeds it
            dt = jnp.where(valid[..., None],
                           jax.nn.softplus(dt + p[pre + "dt_bias"]), 0.0)
            y, state = ssm.ssm_scan_chunked(
                x, dt, -jnp.exp(p[pre + "A_log"]), Bm, Cm, p[pre + "D"],
                chunk=self.chunk, dtype=self.dtype)
            out = self._mamba_out(p, i, y, z)
        return out, state, tail.reshape(tail.shape[:1] + self._tail_shape)

    def mamba_step(self, p, i, a, rows, pools, slots):
        """One token a row ``a (B, U)`` on the state that ``slots`` (the
        cache's ``SlotState``) keeps at state rows ``rows (B,)`` of this
        layer: returns ``(output (B, U), pools)``.

        The recurrence has ONE form for every batch: on the chip the kernel
        ``ops.pallas_kernels.ssm_step_slots``, which gets the whole state
        pool through ``slots.in_place`` and moves each LIVE row's state
        from the pool once and back once, found by its state row (a padded
        row and a slot no row names move nothing: 2.4 ms for 23 layers at
        14 live rows where a pass over every slot took 7.6, ``PERF.md``,
        PR 31); where the program is lowered for the CPU its definition,
        ``slots.read`` -> ``ops.ssm.ssm_step`` -> ``slots.write``
        (``by_platform``: nothing a caller sets chooses, and
        ``ssm.step.path`` counts which was built)."""
        import jax
        import jax.numpy as jnp
        from ...ops import ssm
        from ...ops.pallas_kernels import by_platform, ssm_step_slots
        pre = f"l{i}_"
        n = self._nth[i]
        with jax.named_scope("ssm.mix"):
            z, xbc, dt = self._mamba_inputs(p, i, a)
        with jax.named_scope("ssm.conv"):
            (tail,) = slots.read(pools, n, rows, ("conv",))
            tail, xc = ssm.conv_step(
                tail.reshape(tail.shape[0], self.conv_kernel - 1, -1), xbc,
                p[pre + "conv_w"], p[pre + "conv_b"])
            pools = slots.write(
                pools, n, rows,
                (tail.reshape(tail.shape[:1] + self._tail_shape),),
                ("conv",))
        with jax.named_scope("ssm.mix"):
            x, Bm, Cm = self._mamba_split(xc)
            dt = jax.nn.softplus(dt + p[pre + "dt_bias"])
            step = (x, dt, -jnp.exp(p[pre + "A_log"]), Bm, Cm, p[pre + "D"])

            def kernel(pools, *step):
                return slots.in_place(
                    pools, n, rows, "ssm", lambda pool, layer, rows:
                    ssm_step_slots(pool, layer, rows, *step))

            def plain(pools, *step):
                (state,) = slots.read(pools, n, rows, ("ssm",))
                state, y = ssm.ssm_step(state, *step)
                return slots.write(pools, n, rows, (state,), ("ssm",)), y

            pools, y = by_platform("ssm.step.path", tuple(pools), *step,
                                   kernel=kernel, plain=plain,
                                   rows=a.shape[0])
            out = self._mamba_out(p, i, y, z)
        return out, pools

    def _qkv(self, p, i, a):
        """``(q (..., kv_heads, q a kv head, D) float32, k, v (...,
        kv_width) in the cache dtype)``: query head ``j`` sits at ``[j //
        r, j % r]``, under the KV head it reads."""
        pre = f"l{i}_"
        q = _dot(a, p[pre + "wq"]).reshape(
            a.shape[:-1] + (self.kv_heads, self.q_heads // self.kv_heads,
                            self.head_dim))
        return q, _dot(a, p[pre + "wk"]).astype(self.dtype), \
            _dot(a, p[pre + "wv"]).astype(self.dtype)

    def attend(self, p, i, q, k, v, mask):
        """Grouped-query attention of ``q (B, Q, g, r, D)`` over ``k``, ``v
        (B, L, kv_width)`` (stored precision) where ``mask (B, Q, L)``
        allows.  Returns the attention output ``(B, Q, U)``."""
        return _dot(self.attend_heads(q, k, v, mask), p[f"l{i}_wo"])

    def attend_heads(self, q, k, v, mask):
        """:meth:`attend` before the output projection: the heads' outputs
        side by side, ``(B, Q, heads * D)`` float32."""
        return gqa_heads(q, k, v, mask, self.kv_heads, self.head_dim,
                         self.dtype)

    def _experts(self, p, i, m, valid, counts):
        """Routed share + shared expert of flat rows ``m (T, U)``."""
        import jax
        pre = f"l{i}_"
        y, rows, n_assign = routed_relu2_share(
            m, p[pre + "router"], p[pre + "exp_wu"], p[pre + "exp_wd"],
            self.held, top_k=self.top_k, n_group=self.n_group,
            topk_group=self.topk_group, scale=self.routed_scale, valid=valid)
        counts.append((rows, n_assign))
        with jax.named_scope("moe.shared"):
            shared = _relu2(m, p[pre + "sh_wu"], p[pre + "sh_wd"])
        return y + shared

    def prefill_math(self, p, tokens, lengths):
        """Pure prefill: ``(last_logits, k_rows, v_rows, ssm, conv)`` — see
        the class docstring.  Padded positions are routed to no expert and
        leave every recurrent state alone."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        h = p["embed"][tokens].astype(jnp.float32)
        pos = jnp.arange(S, dtype=jnp.int32)
        valid = pos[None, :] < lengths[:, None]
        causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)),
                                  (B, S, S))
        ks, vs, states, tails, counts = [], [], [], [], []
        for i, kind in enumerate(self.pattern):
            a = _rms(h, p[f"l{i}_norm"], self.eps)
            if kind == "M":
                o, state, tail = self.mamba_prefill(p, i, a, valid, lengths)
                states.append(state)
                tails.append(tail)
            elif kind == "*":
                with jax.named_scope("attn.gqa"):
                    q, k, v = self._qkv(p, i, a)
                    o = self.attend(p, i, q, k, v, causal)
                ks.append(k)
                vs.append(v)
            else:
                o = self._experts(p, i, a.reshape(B * S, -1),
                                  valid.reshape(-1), counts).reshape(B, S, -1)
            h = h + o
        last = _rms(h[jnp.arange(B), lengths - 1], p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(last, p["head"])
        return (logits, jnp.stack(ks), jnp.stack(vs), jnp.stack(states),
                jnp.stack(tails))

    def step_program(self, p, tokens, positions, tables, pools, pages):
        """Pure fused decode step, one token a row.  ``tables`` ends with
        each row's state slot (``pages.addresses``): a Mamba layer advances
        the slot's state through ``pages.state`` (:meth:`mamba_step`); an
        attention layer writes the row's K/V into its page and attends
        over the row's paged context through ``pages.attend``.  Padded rows
        (page table all trash) use the trash slot and are routed to no
        expert.
        Returns ``(logits (B, vocab), pools, (moe_rows (expert layers, held
        + 1) int32, live rows (1,) int32))``."""
        import jax
        import jax.numpy as jnp
        ptab, srow = pages.addresses(tables)
        page_size = pages.page_size
        h = p["embed"][tokens].astype(jnp.float32)
        wp = jnp.take_along_axis(ptab, (positions // page_size)[:, None],
                                 axis=1)[:, 0]
        woff = positions % page_size
        valid = ptab[:, 0] != 0
        counts = []
        for i, kind in enumerate(self.pattern):
            a = _rms(h, p[f"l{i}_norm"], self.eps)
            n = self._nth[i]
            if kind == "M":
                o, pools = self.mamba_step(p, i, a, srow, pools, pages.state)
            elif kind == "*":
                with jax.named_scope("attn.gqa"):
                    q, k, v = self._qkv(p, i, a)
                    pools = pages.write(pools, n, wp, woff, (k, v))
                    o = _dot(pages.attend(
                        pools, n, ptab, positions, q,
                        lambda ck, cv, mask: self.attend_heads(
                            q[:, None], ck, cv, mask)[:, 0]), p[f"l{i}_wo"])
            else:
                o = self._experts(p, i, a, valid, counts)
            h = h + o
        hf = _rms(h, p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(hf, p["head"])
        return logits, pools, (moe_rows_of(counts, len(self.held)),
                               valid.sum().astype(jnp.int32)[None])

    def commit_program(self, state, lengths, tables, pools, pages):
        """Store a prefill's ``(k_rows, v_rows, ssm, conv)``: the K/V rows
        in the pages ``tables`` names, a layer at a time, and each Mamba
        layer's state and tail as the WHOLE state of the row's slot —
        whatever the slot's last owner left is overwritten here, before any
        step reads it."""
        k_rows, v_rows, ssm_state, tails = state
        ptab, srow = pages.addresses(tables)
        dest_page, dest_off = commit_destinations(
            k_rows.shape[2], lengths, ptab, pages.page_size)
        for n in range(len(self.attn_layers)):
            pools = pages.write(pools, n, dest_page, dest_off,
                                (k_rows[n], v_rows[n]))
        for n in range(len(self.mamba_layers)):
            pools = pages.state.write(pools, n, srow,
                                      (ssm_state[n], tails[n]))
        return pools

    sample_math = staticmethod(sample_math)

    def record_step_extras(self, extras, model):
        """Telemetry from one step's counts (the program's vector of them,
        flat): the ``decode.moe.*`` counters :class:`LatentMoELM` emits,
        and ``decode.ssm.layer_steps`` / ``decode.ssm.state_rows`` (Mamba
        layers run, and live rows' states they read and wrote)."""
        extras = np.asarray(extras)
        live = int(extras[-1])
        _tel.count("decode.ssm.layer_steps", len(self.mamba_layers),
                   model=model)
        _tel.count("decode.ssm.state_rows", live * len(self.mamba_layers),
                   model=model)
        record_moe_rows(extras[:-1].reshape(-1, len(self.held) + 1), model)

    # ------------------------------------------------------- gluon frontend
    def hybrid_forward(self, F, tokens, lengths, **params):
        if not isinstance(tokens, NDArray) and not hasattr(tokens, "_data"):
            raise NotImplementedError(
                "HybridSSMMoELM has no symbolic frontend (export is not "
                "supported); the decode runtime compiles it through "
                "compile_grid / the CachedOp path instead")
        leaves = [params[n] for n in self._param_order]

        def pure(tok, ln_, *leaf_vals):
            return self.prefill_math(self._params_dict(leaf_vals), tok, ln_)

        return tuple(invoke_fn(pure, [tokens, lengths] + leaves,
                               op_name="hybrid_ssm_moe_prefill"))
