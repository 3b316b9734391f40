"""Linear-attention / gated grouped-query attention, routed-expert decode
model (the ``solar_open2`` family's block, as Solar-Open2-250B publishes it)
— a fifth block beside :class:`CausalLM`, :class:`LatentMoELM`,
:class:`HybridSSMMoELM` and :class:`WindowMoELM` behind the same runtime,
scheduler and cache.

Every layer is ``h <- h + mixer(RMSNorm(h))``, ``h <- h + experts(RMSNorm(
h))``.  The mixer is grouped-query attention at the layers ``gqa_layers``
names and a linear-attention layer everywhere else (three to one as
published):

- **KDA** — the gated delta rule with a decay a key channel
  (``mxnet_tpu.ops.delta_rule``): ``q``, ``k``, ``v`` each through its own
  projection, a depthwise causal convolution of ``conv_kernel`` taps and
  SiLU, ``q`` and ``k`` L2-normalised a head (``q`` times ``dk^-0.5``); a
  log-decay ``g = -exp(A_log) softplus((x Wf1) Wf2 + dt_bias)`` a key
  channel through a low-rank gate; ``beta = 2 sigmoid(x Wb)`` a head (the
  family's ``allow_neg_eigval``); the recurrence ``S <- diag(e^g) S``, ``u =
  beta (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q`` on a ``(dk, dv)``
  matrix a head; an RMS norm over each head's ``dv`` with a sigmoid gate
  through a second low-rank projection, an output projection.  Its state is
  **per sequence, not per token**: the matrix state ``(heads, dk, dv)`` in
  float32 and the three convolutions' last ``conv_kernel - 1`` inputs, kept
  in the cache's *state pools* a slot (:meth:`cache_layout`'s ``state``
  section; ``kv_format.SlotState``).  Prefill runs the **chunked** form
  (``delta_rule_chunked``: a triangular solve inside each chunk) and hands
  over the state and the tails **as of each row's true length** (padding
  behind it has ``beta`` = ``g`` = 0); the step advances each live row's
  state where it lies in the donated pool, in one kernel a layer
  (``ops.pallas_kernels.kda_step_slots``; where the program is lowered for
  the CPU, its definition ``delta_rule_step`` between the slot's ``read``
  and ``write``).
- **GQA** — ``num_attention_heads`` queries over ``num_key_value_heads``
  keys and values, no rotary and no bias (the KDA layers carry position),
  the heads' outputs gated elementwise by ``sigmoid(x W_gate)`` before the
  output projection.  Only these layers page: the K/V pools have one layer
  for each, and a step attends over the row's live pages where they lie
  (``PageFormat.attend``).  Prefill goes by query blocks and holds no
  ``(heads, S, S)`` array.

The expert sublayer of every layer is routed SwiGLU experts plus one shared
expert, unweighted, over the experts ``held_experts`` (one chip's share):
sigmoid scores, the ``top_k`` largest, weights normalised times
``routed_scaling_factor`` (``parallel.moe.route_to_held``).  The products
are chosen by the rows a program brings, which it can see in its shapes: a
prompt's hundreds of rows hit every held expert and go through the grouped
products (``parallel.moe.routed_expert_share``); a step's few rows hit few
and go by expert, a plain product chain under a conditional for each one
hit (``routed_expert_share_by_expert``: the grouped product read a third of
the chip's bandwidth at 11 rows, ``PERF.md`` PR 37).

Precision and contract as its siblings: weights, K/V rows and the
convolution tails are ``dtype`` (bfloat16 as served; the convolutions'
input is rounded to it in prefill and step alike, so both see what the tail
stores); products in that dtype with float32 accumulation; the residual
stream, norms, softmax, router scores, the decay, ``beta``, the matrix
state and the logits float32.  Held to the plain reference
(``perf/reference/solar_open2.py``, the KDA layer as the sequential
recurrence) within the tolerances ``tests/test_linear_moe_lm.py`` writes
down.

What the block refuses, each with a sentence (none needs code here): a
drafter (there is no verify program: a rejected draft's tokens would have to
be taken out of the matrix state), quantized pools, and a mesh (slot pools
are not sharded).  Prefix sharing is a no-op for it (the state at a prefix
boundary is in no page): the cache makes no lookup and counts
``decode.prefix.skipped``.
"""
from __future__ import annotations

import math

import numpy as np

from ...gluon.block import HybridBlock
from ...ndarray import NDArray, invoke_fn
from ...telemetry import bus as _tel
from .hybrid_moe import gqa_heads
from .latent_moe import (_dot, _rms, _swiglu, moe_rows_of,
                         record_moe_rows)
from .model import commit_destinations, sample_math

__all__ = ["LinearMoELM"]


def _l2(x, scale=1.0):
    """``x / |x|`` over the last axis (eps 1e-6 under the root), times
    ``scale``."""
    import jax
    return x * (jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6) * scale)


class LinearMoELM(HybridBlock):
    """Decoder-only stack of KDA linear-attention and gated grouped-query
    layers with routed + shared experts; see the module docstring.
    ``forward(tokens (B, S), lengths (B,))`` returns ``(last_logits (B,
    vocab) float32, k_rows, v_rows (GQA layers, B, S, kv_heads * head_dim),
    kda (KDA layers, B, heads, dk, dv) float32, conv (KDA layers, B) + the
    stored tails' shape)`` for the runtime's commit program.

    ``gqa_layers`` are the depths of the grouped-query layers; at least one
    layer of each kind.  ``held_experts`` are the global ids of the routed
    experts held here (default: all); the router is always
    ``n_routed_experts`` wide.  ``vocab_size`` is the slice of the
    vocabulary held here."""

    def __init__(self, vocab_size=512, hidden_size=64, num_layers=5,
                 gqa_layers=(0, 4), num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, kda_num_heads=4,
                 kda_head_dim=16, conv_kernel=4, gate_rank=None,
                 chunk_size=16, moe_intermediate_size=32, n_routed_experts=16,
                 held_experts=None, num_experts_per_tok=4,
                 routed_scaling_factor=1.0, norm_eps=1e-5, max_length=128,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self.vocab_size, self.units = int(vocab_size), int(hidden_size)
        self.num_layers = int(num_layers)
        self.gqa_layers = tuple(sorted(int(i) for i in gqa_layers
                                       if int(i) < self.num_layers))
        self.kda_layers = tuple(i for i in range(self.num_layers)
                                if i not in self.gqa_layers)
        if not self.gqa_layers or not self.kda_layers:
            raise ValueError(
                f"gqa_layers={tuple(gqa_layers)} of {self.num_layers} "
                f"layers: the block needs a grouped-query layer and a "
                f"linear-attention layer")
        # a layer's index among its own kind: its layer of the page pools /
        # of the state pools
        self._nth = {i: n for ls in (self.gqa_layers, self.kda_layers)
                     for n, i in enumerate(ls)}
        self.q_heads, self.kv_heads = int(num_attention_heads), \
            int(num_key_value_heads)
        if self.q_heads % self.kv_heads:
            raise ValueError(
                f"num_attention_heads={self.q_heads} is not divisible by "
                f"num_key_value_heads={self.kv_heads}")
        self.head_dim = int(head_dim)
        self.kv_width = self.kv_heads * self.head_dim
        self.k_heads, self.k_dim = int(kda_num_heads), int(kda_head_dim)
        self.k_width = self.k_heads * self.k_dim
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk_size)
        self.n_routed = int(n_routed_experts)
        self.held = tuple(range(self.n_routed)) if held_experts is None \
            else tuple(int(e) for e in held_experts)
        if not self.held or len(set(self.held)) != len(self.held) or \
                not all(0 <= e < self.n_routed for e in self.held):
            raise ValueError(
                f"held_experts={self.held} must be distinct ids in "
                f"[0, {self.n_routed})")
        self.top_k = int(num_experts_per_tok)
        self.routed_scale = float(routed_scaling_factor)
        self.eps = float(norm_eps)
        self.max_length = int(max_length)
        self.dtype = str(dtype)
        u, wd, G = self.units, self.dtype, len(self.held)
        kw, f = self.k_width, int(moe_intermediate_size)
        rank = self.k_dim if gate_rank is None else int(gate_rank)

        def reg(name, shape, init="normal", dtype=wd):
            setattr(self, name, self.params.get(name, shape=shape, init=init,
                                                dtype=dtype))

        from ... import initializer as _init
        reg("embed", (self.vocab_size, u))
        reg("head", (u, self.vocab_size))
        reg("norm_f", (u,), "ones", "float32")
        for i in range(self.num_layers):
            p = f"l{i}_"
            reg(p + "norm_mix", (u,), "ones", "float32")
            if i in self.gqa_layers:
                reg(p + "wq", (u, self.q_heads * self.head_dim))
                reg(p + "wk", (u, self.kv_width))
                reg(p + "wv", (u, self.kv_width))
                reg(p + "wgate", (u, self.q_heads * self.head_dim))
                reg(p + "wo", (self.q_heads * self.head_dim, u))
            else:
                for x in "qkv":
                    reg(p + "w" + x, (u, kw))
                    reg(p + "conv_" + x, (kw, self.conv_kernel),
                        dtype="float32")
                reg(p + "wf1", (u, rank))
                reg(p + "wf2", (rank, kw))
                # the family draws these a head and a channel; a host
                # initialiser gives each the middle of its range (A 4, a
                # step of 0.01: a channel keeps exp(-0.04) of itself a token)
                reg(p + "A_log", (self.k_heads,),
                    _init.Constant(math.log(4.0)), "float32")
                reg(p + "dt_bias", (kw,),
                    _init.Constant(math.log(math.expm1(0.01))), "float32")
                reg(p + "wb", (u, self.k_heads))
                reg(p + "wg1", (u, rank))
                reg(p + "wg2", (rank, kw))
                reg(p + "bg", (kw,), "zeros", "float32")
                reg(p + "norm_o", (self.k_dim,), "ones", "float32")
                reg(p + "wo", (kw, u))
            reg(p + "norm_ffn", (u,), "ones", "float32")
            # float32 router scores at the highest precision, so that the
            # choice of experts follows the reference's
            reg(p + "router", (u, self.n_routed), dtype="float32")
            reg(p + "exp_wg", (G, u, f))
            reg(p + "exp_wu", (G, u, f))
            reg(p + "exp_wd", (G, f, u))
            reg(p + "sh_wg", (u, f))
            reg(p + "sh_wu", (u, f))
            reg(p + "sh_wd", (f, u))
        self._param_order = sorted(self._reg_params)

    # ------------------------------------------------- what the runtime reads
    #: queries a block of the prompt's grouped-query attention: scores of
    #: (heads, 256, S) float32 at a time, 134 MB at 64 heads and 2,048 keys
    attention_block = 256

    #: the most rows the expert sublayer serves by expert and not by grouped
    #: products: every step's batch, no prefill bucket
    few_rows = 64

    #: one prompt a prefill call: the in-chunk solves and the attention
    #: scores are per row, and a prompt of a chunk or more already fills the
    #: MXU's rows
    max_prefill_batch = 1

    @property
    def _tail_shape(self):
        """A slot's three convolution tails as stored: ``(K - 1) * 3 *
        heads * dk`` values, oldest input first and ``q | k | v`` side by
        side inside one input, as rows of 128 lanes where they divide (a
        pool whose minor dimensions are whole tiles is updated where it
        lies: ``HybridSSMMoELM._tail_shape`` says what a flat row costs)."""
        tail = (self.conv_kernel - 1) * 3 * self.k_width
        return (tail // 128, 128) if tail % 128 == 0 else (tail,)

    def cache_layout(self):
        """Two kinds of state.  Paged: K and V pools with one layer for
        each grouped-query layer, a row of ``kv_heads * head_dim`` values in
        the block's dtype.  A slot: for each KDA layer the matrix state
        (float32) and the three convolutions' tails.  Not quantizable, not
        sharded."""
        return {"layers": len(self.gqa_layers),
                "pools": (("k", self.kv_width, self.dtype),
                          ("v", self.kv_width, self.dtype)),
                "quantizable": False, "shard_heads": None,
                "max_length": self.max_length,
                "state": {"layers": len(self.kda_layers),
                          "arrays": (("kda", (self.k_heads, self.k_dim,
                                              self.k_dim), "float32"),
                                     ("conv", self._tail_shape,
                                      self.dtype))}}

    def prefill_state(self, b, s):
        """Shapes and dtypes of what :meth:`prefill_math` emits behind the
        logits: K rows, V rows, matrix states, convolution tails."""
        La, Lk = len(self.gqa_layers), len(self.kda_layers)
        kv = ((La, b, s, self.kv_width), self.dtype)
        return (kv, kv,
                ((Lk, b, self.k_heads, self.k_dim, self.k_dim), "float32"),
                ((Lk, b) + self._tail_shape, self.dtype))

    def _params_dict(self, leaves):
        return dict(zip(self._param_order, leaves))

    def param_leaves(self):
        return [self._reg_params[n].data()._data for n in self._param_order]

    # ------------------------------------------------------------ pure math
    def _kda_inputs(self, p, i, a):
        """The three projections of ``a (..., U)`` side by side, ``q | k |
        v``, in the stored dtype: what the convolutions read and their tail
        keeps."""
        import jax.numpy as jnp
        pre = f"l{i}_"
        return jnp.concatenate([_dot(a, p[pre + "w" + x]) for x in "qkv"],
                               axis=-1).astype(self.dtype)

    def _conv(self, p, i):
        """``(taps (3 * heads * dk, K), bias)`` of the three convolutions
        side by side as ``ops.ssm``'s functions take them; the family's have
        no bias."""
        import jax.numpy as jnp
        w = jnp.concatenate([p[f"l{i}_conv_{x}"] for x in "qkv"], axis=0)
        return w, jnp.zeros(w.shape[:1], jnp.float32)

    def _kda_gates(self, p, i, a, xc):
        """``(q, k (..., H, dk), v (..., H, dv), g (..., H, dk) <= 0, beta
        (..., H))`` of the mixer's input ``a (..., U)`` and the convolved
        ``xc (..., 3 * heads * dk)``: heads split, ``q`` and ``k``
        normalised, the decay a key channel and the step a head."""
        import jax
        import jax.numpy as jnp
        pre = f"l{i}_"
        heads = a.shape[:-1] + (self.k_heads, self.k_dim)
        q, k, v = (x.reshape(heads) for x in jnp.split(xc, 3, axis=-1))
        dt = jax.nn.softplus(_dot(_dot(a, p[pre + "wf1"]), p[pre + "wf2"])
                             + p[pre + "dt_bias"])
        g = -jnp.exp(p[pre + "A_log"])[:, None] * dt.reshape(heads)
        beta = 2.0 * jax.nn.sigmoid(_dot(a, p[pre + "wb"]))
        return _l2(q, self.k_dim ** -0.5), _l2(k), v, g, beta

    def _kda_out(self, p, i, a, o):
        """The head-wise gated norm of ``o (..., H, dv)`` and the output
        projection."""
        import jax
        pre = f"l{i}_"
        gate = jax.nn.sigmoid(_dot(_dot(a, p[pre + "wg1"]), p[pre + "wg2"])
                              + p[pre + "bg"])
        y = _rms(o, p[pre + "norm_o"], self.eps).reshape(gate.shape) * gate
        return _dot(y, p[pre + "wo"])

    def kda_prefill(self, p, i, a, valid, lengths):
        """The KDA mixer over whole sequences ``a (B, S, U)``: returns
        ``(output (B, S, U), state (B, H, dk, dv) float32, tails (B,) + the
        stored shape)``, the state and the tails as of ``lengths``."""
        import jax
        import jax.numpy as jnp
        from ...ops import ssm
        from ...ops.delta_rule import delta_rule_chunked
        with jax.named_scope("kda.mix"):
            qkv = self._kda_inputs(p, i, a)
        with jax.named_scope("kda.conv"):
            xc = ssm.causal_conv(qkv, *self._conv(p, i))
            tail = ssm.conv_tail(qkv, lengths, self.conv_kernel)
        with jax.named_scope("kda.mix"):
            q, k, v, g, beta = self._kda_gates(p, i, a, xc)
            # padding neither decays the state nor feeds it
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        with jax.named_scope("kda.recur"):
            o, state = delta_rule_chunked(q, k, v, g, beta, chunk=self.chunk,
                                          dtype=self.dtype)
        with jax.named_scope("kda.mix"):
            out = self._kda_out(p, i, a, o)
        return out, state, tail.reshape(tail.shape[:1] + self._tail_shape)

    def kda_step(self, p, i, a, rows, pools, slots):
        """One token a row ``a (B, U)`` on the state that ``slots`` (the
        cache's ``SlotState``) keeps at state rows ``rows (B,)`` of this
        layer: returns ``(output (B, U), pools)``.

        The recurrence has ONE form for every batch: on the chip the kernel
        ``ops.pallas_kernels.kda_step_slots``, which gets the whole state
        pool through ``slots.in_place`` and moves each LIVE row's state
        from the pool once and back once, found by its state row (a padded
        row and a slot no row names move nothing); where the program is
        lowered for the CPU its definition, ``slots.read`` ->
        ``ops.delta_rule.delta_rule_step`` -> ``slots.write``
        (``by_platform``: nothing a caller sets chooses, and
        ``kda.step.path`` counts which was built)."""
        import jax
        from ...ops import ssm
        from ...ops.delta_rule import delta_rule_step
        from ...ops.pallas_kernels import by_platform, kda_step_slots
        n = self._nth[i]
        with jax.named_scope("kda.mix"):
            qkv = self._kda_inputs(p, i, a)
        with jax.named_scope("kda.conv"):
            (tail,) = slots.read(pools, n, rows, ("conv",))
            tail, xc = ssm.conv_step(
                tail.reshape(tail.shape[0], self.conv_kernel - 1, -1), qkv,
                *self._conv(p, i))
            pools = slots.write(
                pools, n, rows,
                (tail.reshape(tail.shape[:1] + self._tail_shape),),
                ("conv",))
        with jax.named_scope("kda.mix"):
            step = self._kda_gates(p, i, a, xc)
        with jax.named_scope("kda.recur"):
            def kernel(pools, *step):
                return slots.in_place(
                    pools, n, rows, "kda", lambda pool, layer, rows:
                    kda_step_slots(pool, layer, rows, *step))

            def plain(pools, *step):
                (state,) = slots.read(pools, n, rows, ("kda",))
                state, o = delta_rule_step(state, *step)
                return slots.write(pools, n, rows, (state,), ("kda",)), o

            pools, o = by_platform("kda.step.path", tuple(pools), *step,
                                   kernel=kernel, plain=plain,
                                   rows=a.shape[0])
        with jax.named_scope("kda.mix"):
            out = self._kda_out(p, i, a, o)
        return out, pools

    def _qkv(self, p, i, a):
        """``(q (..., kv_heads, q a kv head, D) float32, k, v (...,
        kv_width) in the cache dtype)``: query head ``j`` sits at ``[j //
        r, j % r]``, under the KV head it reads.  No rotary."""
        pre = f"l{i}_"
        q = _dot(a, p[pre + "wq"]).reshape(
            a.shape[:-1] + (self.kv_heads, self.q_heads // self.kv_heads,
                            self.head_dim))
        return q, _dot(a, p[pre + "wk"]).astype(self.dtype), \
            _dot(a, p[pre + "wv"]).astype(self.dtype)

    def attend_heads(self, q, k, v, mask):
        """Grouped-query attention of ``q (B, Q, g, r, D)`` over ``k``, ``v
        (B, L, kv_width)`` (stored precision) where ``mask (B, Q, L)``
        allows: the heads' outputs side by side, ``(B, Q, heads * D)``
        float32, before the gate and the output projection."""
        return gqa_heads(q, k, v, mask, self.kv_heads, self.head_dim,
                         self.dtype)

    def attend_prompt(self, q, k, v):
        """Causal attention of a whole padded prompt, ``q (B, S, g, r, D)``
        over its own ``k``, ``v (B, S, kv_width)``, by query blocks of
        ``attention_block`` tokens mapped one after another over all the
        keys: ``(heads, block, S)`` scores at a time and never a ``(heads,
        S, S)`` array.  Returns ``(B, S, heads * D)`` float32."""
        import jax
        import jax.numpy as jnp
        B, S = q.shape[:2]
        W = min(self.attention_block, S)
        nb = -(-S // W)
        if nb * W != S:
            q = jnp.pad(q, ((0, 0), (0, nb * W - S)) + ((0, 0),) * 3)
        keys, a = jnp.arange(S), jnp.arange(W)

        def block(args):
            qb, n = args
            mask = keys[None, :] <= (n * W + a)[:, None]
            return self.attend_heads(
                qb, k, v, jnp.broadcast_to(mask, (B,) + mask.shape))

        qs = q.reshape((B, nb, W) + q.shape[2:]).swapaxes(0, 1)
        o = jax.lax.map(block, (qs, jnp.arange(nb)))
        return o.swapaxes(0, 1).reshape(B, nb * W, -1)[:, :S]

    def _gqa_out(self, p, i, a, o):
        """The elementwise output gate and the output projection."""
        import jax
        pre = f"l{i}_"
        return _dot(o * jax.nn.sigmoid(_dot(a, p[pre + "wgate"])),
                    p[pre + "wo"])

    def _experts(self, p, i, h, valid, counts):
        """``h + experts(RMSNorm(h))`` over flat rows ``h (T, U)``: this
        chip's routed share plus the shared expert."""
        import jax
        from ...parallel import moe
        pre = f"l{i}_"
        m = _rms(h, p[pre + "norm_ffn"], self.eps)
        # a step's few rows hit few experts: a plain product chain for each
        # one hit; a prompt's rows hit them all: the grouped products
        share = moe.routed_expert_share_by_expert \
            if h.shape[0] <= self.few_rows else moe.routed_expert_share
        y, rows, n_assign = share(
            m, p[pre + "router"], p[pre + "exp_wg"], p[pre + "exp_wu"],
            p[pre + "exp_wd"], self.held, top_k=self.top_k,
            scale=self.routed_scale, valid=valid)
        counts.append((rows, n_assign))
        with jax.named_scope("moe.shared"):
            shared = _swiglu(m, p[pre + "sh_wg"], p[pre + "sh_wu"],
                             p[pre + "sh_wd"])
        return h + y + shared

    def prefill_math(self, p, tokens, lengths):
        """Pure prefill: ``(last_logits, k_rows, v_rows, kda, conv)`` — see
        the class docstring.  Padded positions are routed to no expert and
        leave every matrix state alone."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        h = p["embed"][tokens].astype(jnp.float32)
        pos = jnp.arange(S, dtype=jnp.int32)
        valid = pos[None, :] < lengths[:, None]
        ks, vs, states, tails, counts = [], [], [], [], []
        for i in range(self.num_layers):
            a = _rms(h, p[f"l{i}_norm_mix"], self.eps)
            if i in self.gqa_layers:
                with jax.named_scope("attn.gqa"):
                    q, k, v = self._qkv(p, i, a)
                    o = self._gqa_out(p, i, a, self.attend_prompt(q, k, v))
                ks.append(k)
                vs.append(v)
            else:
                o, state, tail = self.kda_prefill(p, i, a, valid, lengths)
                states.append(state)
                tails.append(tail)
            h = self._experts(p, i, (h + o).reshape(B * S, -1),
                              valid.reshape(-1), counts).reshape(B, S, -1)
        last = _rms(h[jnp.arange(B), lengths - 1], p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(last, p["head"])
        return (logits, jnp.stack(ks), jnp.stack(vs), jnp.stack(states),
                jnp.stack(tails))

    def step_program(self, p, tokens, positions, tables, pools, pages):
        """Pure fused decode step, one token a row.  ``tables`` ends with
        each row's state slot (``pages.addresses``): a KDA layer advances
        the slot's state through ``pages.state`` (:meth:`kda_step`); a
        grouped-query layer writes the row's K/V into its page and attends
        over the row's paged context through ``pages.attend``.  Padded rows
        (page table all trash) use the trash slot and are routed to no
        expert.  Returns ``(logits (B, vocab), pools, (moe_rows (layers,
        held + 1) int32, live rows (1,) int32))``."""
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
        for i in range(self.num_layers):
            a = _rms(h, p[f"l{i}_norm_mix"], self.eps)
            n = self._nth[i]
            if i in self.gqa_layers:
                with jax.named_scope("attn.gqa"):
                    q, k, v = self._qkv(p, i, a)
                    pools = pages.write(pools, n, wp, woff, (k, v))
                    o = self._gqa_out(p, i, a, pages.attend(
                        pools, n, ptab, positions, q,
                        lambda ck, cv, mask: self.attend_heads(
                            q[:, None], ck, cv, mask)[:, 0]))
            else:
                o, pools = self.kda_step(p, i, a, srow, pools, pages.state)
            h = self._experts(p, i, h + o, valid, counts)
        hf = _rms(h, p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(hf, p["head"])
        return logits, pools, (moe_rows_of(counts, len(self.held)),
                               valid.sum().astype(jnp.int32)[None])

    def commit_program(self, state, lengths, tables, pools, pages):
        """Store a prefill's ``(k_rows, v_rows, kda, conv)``: the K/V rows
        in the pages ``tables`` names, a layer at a time, and each KDA
        layer's state and tails as the WHOLE state of the row's slot —
        whatever the slot's last owner left is overwritten here, before any
        step reads it."""
        k_rows, v_rows, kda_state, tails = state
        ptab, srow = pages.addresses(tables)
        dest_page, dest_off = commit_destinations(
            k_rows.shape[2], lengths, ptab, pages.page_size)
        for n in range(len(self.gqa_layers)):
            pools = pages.write(pools, n, dest_page, dest_off,
                                (k_rows[n], v_rows[n]))
        for n in range(len(self.kda_layers)):
            pools = pages.state.write(pools, n, srow,
                                      (kda_state[n], tails[n]))
        return pools

    sample_math = staticmethod(sample_math)

    def record_step_extras(self, extras, model):
        """Telemetry from one step's counts (the program's vector of them,
        flat): the ``decode.moe.*`` counters of the shared expert layer, and
        ``decode.kda.layer_steps`` / ``decode.kda.state_rows`` (KDA layers
        run, and live rows' matrix states they read and wrote)."""
        extras = np.asarray(extras)
        live = int(extras[-1])
        _tel.count("decode.kda.layer_steps", len(self.kda_layers),
                   model=model)
        _tel.count("decode.kda.state_rows", live * len(self.kda_layers),
                   model=model)
        record_moe_rows(extras[:-1].reshape(-1, len(self.held) + 1), model)

    # ------------------------------------------------------- gluon frontend
    def hybrid_forward(self, F, tokens, lengths, **params):
        if not isinstance(tokens, NDArray) and not hasattr(tokens, "_data"):
            raise NotImplementedError(
                "LinearMoELM has no symbolic frontend (export is not "
                "supported); the decode runtime compiles it through "
                "compile_grid / the CachedOp path instead")
        leaves = [params[n] for n in self._param_order]

        def pure(tok, ln_, *leaf_vals):
            return self.prefill_math(self._params_dict(leaf_vals), tok, ln_)

        return tuple(invoke_fn(pure, [tokens, lengths] + leaves,
                               op_name="linear_moe_prefill"))
