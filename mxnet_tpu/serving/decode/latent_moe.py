"""Latent-attention, routed-expert decode model (the DeepSeek-V3 family's
block, as A.X-K1 publishes it) — a second block beside :class:`CausalLM`
behind the same runtime, scheduler and paged cache.

What differs from :class:`~mxnet_tpu.serving.decode.model.CausalLM`:

- **RMSNorm, SwiGLU, an untied head, rotary positions** (YaRN-scaled, on a
  ``qk_rope_head_dim``-wide slice of every query and on ONE key shared by
  all heads).  There is no position table: ``max_length`` is the context
  the block is built for, inside the rotary range.
- **Latent attention (MLA).**  Per token and layer the cache holds one row
  ``(c_kv | k_r)`` of ``kv_lora_rank + qk_rope_head_dim`` values shared by
  every head — :meth:`cache_layout` says so and the paged cache builds ONE
  pool of that row, padded to whole 128-lane tiles.  Prefill runs the *expanded* form (per-head keys
  and values from ``c_kv W_kvb``); the decode step runs the *absorbed* form
  (``W_kvb`` folded into the query and the output, scores and context taken
  straight over the cached latent rows), so no per-head K/V is ever
  materialised for a cached token.  Same mathematics, two contractions.
  :meth:`LatentMoELM.attend_expanded` is the expanded form's definition
  (every head's ``(S, S)`` scores, one softmax); a prefill program lowered
  for the chip runs it by blocks, one kernel a layer and no such array
  (:meth:`LatentMoELM._scores_lowered`).
- **A dense FFN in the first ``first_k_dense_replace`` layers, then routed +
  shared experts.**  The routed part is
  :func:`mxnet_tpu.parallel.moe.routed_expert_share`: this block holds the
  experts ``held_experts`` (one chip's share of an expert-parallel
  deployment), routes over all ``n_routed_experts``, and computes its own
  experts' part of the sum.  The step program also returns, per expert
  layer, the rows each held expert received (:meth:`record_step_extras`
  turns them into the ``decode.moe.*`` counters).
- **Precision and the contract.**  Weights and cache rows are ``dtype``
  (bfloat16 as served); every weight product is a ``dot_general`` in that
  dtype on the MXU with float32 accumulation; the residual stream, norms,
  rotary angles, softmax, router scores and logits are float32.  Nothing
  goes through ``rowdot``, so the contract is NOT bitwise row stability but
  agreement with the plain reference (``perf/reference/axk1.py``) within
  the tolerances ``tests/test_latent_moe.py`` writes down.  ``dtype=
  "float32"`` (tests) runs the same programs at the highest precision.

- **The residual path** is ``h + f(norm(h))`` with ``hc_mult == 1`` and
  manifold-constrained hyper-connections with ``hc_mult == n > 1``
  (:mod:`mxnet_tpu.ops.hyper_connection`, Xing4.0's): the three programs
  then carry ``X (rows, n, hidden)`` float32 between sublayers, every
  sublayer reads ``Hpre X``, and its output is written back as ``Hres X +
  Hpost^T y`` with ``Hres`` made doubly stochastic by ``hc_sinkhorn_iters``
  Sinkhorn rounds.  The streams start as ``n`` copies of the embedding and
  end summed; they are not state between steps, so the cache, the scheduler
  and the runtime see the same block.  :meth:`_sublayer` is the one place
  that adds a sublayer's output or writes it back (``_ffn`` goes through
  it).

No drafter and no quantized latent pool: asking for either raises.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from ...gluon.block import HybridBlock
from ...ndarray import NDArray, invoke_fn
from ...ops import hyper_connection as _hc
from ...telemetry import bus as _tel
from .model import commit_destinations, sample_math

__all__ = ["LatentMoELM", "yarn_inv_freq", "yarn_softmax_scale"]


def yarn_inv_freq(dim, base, scaling=None):
    """Rotary inverse frequencies ``(dim // 2,)`` float64 for a ``dim``-wide
    slice.  With YaRN ``scaling`` (``factor``, ``original_max_position_
    embeddings``, ``beta_fast``, ``beta_slow``) each frequency is a blend of
    itself and itself over ``factor``: pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    pairs that turn fewer than ``beta_slow`` times are slowed ``factor``
    times, and a linear ramp over the pair index joins the two."""
    j = np.arange(0, dim, 2, dtype="float64")
    freq = base ** (-j / dim)
    if not scaling:
        return freq
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype="float64") - low)
                   / (high - low), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def yarn_softmax_scale(qk_head_dim, scaling=None):
    """``qk_head_dim ** -0.5``, times ``m ** 2`` with ``m = 0.1 *
    mscale_all_dim * ln(factor) + 1`` where YaRN sets ``mscale_all_dim``."""
    scale = qk_head_dim ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        scale *= m * m
    return scale


def _rms(x, g, eps):
    import jax
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _dot(a, w):
    """``a (..., K) . w (K, N)`` in the weights' dtype, float32 out."""
    import jax
    import jax.numpy as jnp
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST
                   if w.dtype == jnp.float32 else None)


def _einsum(spec, a, b, dtype):
    import jax
    import jax.numpy as jnp
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST
                      if jnp.dtype(dtype) == jnp.float32 else None)


def _swiglu(x, wg, wu, wd):
    import jax
    return _dot(jax.nn.silu(_dot(x, wg)) * _dot(x, wu), wd)


def record_moe_rows(rows, model):
    """The ``decode.moe.*`` counters from a step's ``rows (expert layers,
    held + 1)``: per layer the rows each held expert received, then the
    assignments made over all experts."""
    if not rows.size:
        return
    held = rows[:, :-1]
    _tel.count("decode.moe.assignments", int(rows[:, -1].sum()), model=model)
    _tel.count("decode.moe.assignments_held", int(held.sum()), model=model)
    _tel.count("decode.moe.experts_hit", int((held > 0).sum()), model=model)
    _tel.count("decode.moe.layer_steps", int(held.shape[0]), model=model)
    _tel.count("decode.moe.max_expert_rows", int(held.max()), model=model)


def moe_rows_of(counts, G):
    """A step program's ``moe_rows (expert layers, G + 1) int32`` from its
    ``counts``, one ``(rows each of the G held experts received,
    assignments made over all experts)`` an expert layer."""
    import jax.numpy as jnp
    if not counts:
        return jnp.zeros((0, G + 1), jnp.int32)
    return jnp.stack([jnp.concatenate([r, n[None]]) for r, n in counts])


class LatentMoELM(HybridBlock):
    """Decoder-only transformer with latent attention and routed + shared
    experts; see the module docstring.  ``forward(tokens (B, S), lengths
    (B,))`` returns ``(last_logits (B, vocab) float32, rows (layers, B, S,
    pool_width))`` — the latent cache rows ``(c_kv | k_r)`` of every
    position, zero-padded to the pool's row, for the runtime's commit
    program.

    ``held_experts`` are the global ids of the routed experts whose weights
    this block holds (default: all of them); the router is always
    ``n_routed_experts`` wide.  ``vocab_size`` is the slice of the
    vocabulary held here (embedding rows and head columns).
    ``select_bias=True`` gives every expert layer the family's ``noaux_tc``
    score-correction bias ``(n_routed_experts,)`` float32: it joins the
    scores for the choice of experts only.  ``hc_mult > 1`` makes the
    residual path ``hc_mult`` streams mixed by hyper-connections (module
    docstring), with ``hc_res_clamp = (lo, hi)`` on the residual logits."""

    def __init__(self, vocab_size=512, hidden_size=64, num_layers=3,
                 num_heads=4, q_lora_rank=32, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=16, held_experts=None,
                 num_experts_per_tok=4, n_shared_experts=1, n_group=4,
                 topk_group=2, routed_scaling_factor=2.5,
                 first_k_dense_replace=1, rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_scaling=None, max_length=128,
                 dtype="bfloat16", hc_mult=1, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0), select_bias=False,
                 **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = int(vocab_size)
        self.units = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), \
            int(kv_lora_rank)
        self.nope_dim, self.rope_dim = int(qk_nope_head_dim), \
            int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.row_width = self.kv_lora_rank + self.rope_dim
        # a pool's row fills whole 128-lane tiles (the chip pads a row to
        # them anyway, and where it is left to choose it stores a ragged
        # row pages-minor, a layout every program would first copy the
        # whole pool out of — kv_cache's module docstring): the tail of a
        # stored row is zeros, and the contractions run over them
        self.pool_width = -(-self.row_width // 128) * 128
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
        if self.n_routed % self.n_group:
            raise ValueError(f"n_routed_experts={self.n_routed} is not "
                             f"divisible by n_group={self.n_group}")
        self.routed_scale = float(routed_scaling_factor)
        self.first_dense = int(first_k_dense_replace)
        self.moe_layers = tuple(range(self.first_dense, self.num_layers))
        self.eps = float(rms_norm_eps)
        self.max_length = int(max_length)
        self.dtype = str(dtype)
        self.hc_mult = int(hc_mult)
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult={hc_mult} must be at least 1")
        self.hc_iters, self.hc_eps = int(hc_sinkhorn_iters), float(hc_eps)
        self.hc_clamp = (float(hc_res_clamp[0]), float(hc_res_clamp[1]))
        self.select_bias = bool(select_bias)
        self._inv_freq = yarn_inv_freq(self.rope_dim, float(rope_theta),
                                       rope_scaling).astype("float32")
        self._scale = yarn_softmax_scale(self.nope_dim + self.rope_dim,
                                         rope_scaling)
        u, H, wd = self.units, self.num_heads, self.dtype
        f_sh = int(moe_intermediate_size) * int(n_shared_experts)
        G = len(self.held)

        def reg(name, shape, init="normal", dtype=wd):
            setattr(self, name, self.params.get(name, shape=shape, init=init,
                                                dtype=dtype))

        reg("embed", (self.vocab_size, u))
        reg("head", (u, self.vocab_size))
        reg("norm_f", (u,), "ones", "float32")
        for i in range(self.num_layers):
            p = f"l{i}_"
            reg(p + "norm_attn", (u,), "ones", "float32")
            reg(p + "wqa", (u, self.q_lora_rank))
            reg(p + "norm_q", (self.q_lora_rank,), "ones", "float32")
            reg(p + "wqb", (self.q_lora_rank,
                            H * (self.nope_dim + self.rope_dim)))
            reg(p + "wkva", (u, self.row_width))
            reg(p + "norm_kv", (self.kv_lora_rank,), "ones", "float32")
            reg(p + "wkvb", (self.kv_lora_rank,
                             H * (self.nope_dim + self.v_dim)))
            reg(p + "wo", (H * self.v_dim, u))
            reg(p + "norm_ffn", (u,), "ones", "float32")
            if self.hc_mult > 1:
                n = self.hc_mult
                for sub in ("attn", "ffn"):
                    reg(p + f"hc_{sub}_phi", (n * u, n * (n + 2)),
                        dtype="float32")
                    reg(p + f"hc_{sub}_a", (3,), "ones", "float32")
                    reg(p + f"hc_{sub}_b", (n * (n + 2),), "zeros",
                        "float32")
            if i < self.first_dense:
                f = int(intermediate_size)
                reg(p + "wg", (u, f))
                reg(p + "wu", (u, f))
                reg(p + "wd", (f, u))
            else:
                f = int(moe_intermediate_size)
                # router scores are float32 at the highest precision, so
                # the choice of experts follows the reference's
                reg(p + "router", (u, self.n_routed), dtype="float32")
                if self.select_bias:
                    reg(p + "select_bias", (self.n_routed,), "zeros",
                        "float32")
                reg(p + "exp_wg", (G, u, f))
                reg(p + "exp_wu", (G, u, f))
                reg(p + "exp_wd", (G, f, u))
                reg(p + "sh_wg", (u, f_sh))
                reg(p + "sh_wu", (u, f_sh))
                reg(p + "sh_wd", (f_sh, u))
        self._param_order = sorted(self._reg_params)

    # ------------------------------------------------- what the runtime reads
    #: one prompt a prefill call.  A prompt of a hundred tokens already
    #: fills the MXU's rows, so a second prompt in the call buys no time
    #: (it is no longer memory that forbids it: lowered for the chip the
    #: attention goes by blocks in one kernel a layer and a prompt's
    #: temporaries are its per-head keys and values, tens of MB).
    max_prefill_batch = 1

    def cache_layout(self):
        """One pool: a token's row is ``(c_kv | k_r)``, shared by all heads,
        stored in the block's dtype and zero-padded to whole lane tiles
        (``pool_width``); not quantizable, not sharded by heads."""
        return {"layers": self.num_layers,
                "pools": (("latent", self.pool_width, self.dtype),),
                "quantizable": False, "shard_heads": None,
                "max_length": self.max_length}

    def prefill_state(self, b, s):
        """Shape and dtype of the cache rows :meth:`prefill_math` emits."""
        return (self.num_layers, b, s, self.pool_width), self.dtype

    def _served(self, name, array):
        """A parameter's array as the programs take it: as registered, but
        a hyper-connection's ``phi`` with its long axis minor (``(n (n + 2),
        n U)``): as registered its 24 columns tile to 128 lanes on the chip,
        7.3 MB a sublayer moved where 1.4 are meant."""
        return array.T if name.endswith("_phi") else array

    def _params_dict(self, leaves):
        return dict(zip(self._param_order, leaves))

    def param_leaves(self):
        """The served weights, in ``_param_order`` (:meth:`_served`): made
        once, when a runtime places them."""
        return [self._served(n, self._reg_params[n].data()._data)
                for n in self._param_order]

    # ------------------------------------------------------------ pure math
    def _rope(self, x, positions):
        """Rotate the interleaved pairs ``(x[2j], x[2j+1])`` of the last
        axis by ``positions * inv_freq[j]``; ``positions`` broadcasts over
        ``x``'s leading axes.  The result lists the pairs' first members,
        then their second (the family's order; a dot product of two
        rotated vectors does not depend on it)."""
        import jax.numpy as jnp
        ang = positions[..., None].astype(jnp.float32) \
            * jnp.asarray(self._inv_freq)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def _queries(self, p, i, a, positions):
        """``(q_nope (..., H, nope), q_rope (..., H, rope))`` rotated at
        ``positions (...)``."""
        pre = f"l{i}_"
        cq = _rms(_dot(a, p[pre + "wqa"]), p[pre + "norm_q"], self.eps)
        q = _dot(cq, p[pre + "wqb"]).reshape(
            a.shape[:-1] + (self.num_heads, self.nope_dim + self.rope_dim))
        return (q[..., :self.nope_dim],
                self._rope(q[..., self.nope_dim:], positions[..., None]))

    def _latent_row(self, p, i, a, positions):
        """The cache row ``(c_kv | k_r | 0...)`` of each token, float32,
        ``pool_width`` wide."""
        import jax.numpy as jnp
        pre = f"l{i}_"
        ckv_kr = _dot(a, p[pre + "wkva"])
        ckv = _rms(ckv_kr[..., :self.kv_lora_rank], p[pre + "norm_kv"],
                   self.eps)
        kr = self._rope(ckv_kr[..., self.kv_lora_rank:], positions)
        pad = jnp.zeros(kr.shape[:-1] + (self.pool_width - self.row_width,),
                        kr.dtype)
        return jnp.concatenate([ckv, kr, pad], axis=-1)

    def _wkvb(self, p, i):
        """``W_kvb`` as ``(c, H, nope + v)``."""
        return p[f"l{i}_wkvb"].reshape(
            self.kv_lora_rank, self.num_heads, self.nope_dim + self.v_dim)

    def _sublayer(self, p, i, sub, h, f, resid=None, live=None):
        """One sublayer (``sub`` is ``"attn"`` or ``"ffn"``) on the residual
        path: ``f`` maps the normed input ``(..., U)`` to the parts of the
        sublayer's output, and this is the one place that adds them to ``h
        (..., U)`` (``hc_mult == 1``) or writes their sum back to the streams
        ``h (..., n, U)``.  ``resid``, a list, receives the sublayer's
        ``sinkhorn_residual`` a row; ``live (...)``, where given, marks the
        rows that are not padding (the kernels skip what they can of the
        others; their coefficients are then a zero stream's).

        The mixing is two halves around ``f`` that hand the tokens'
        coefficients over as one lane tile each (``hyper_connection.
        coef_tile``): where the program is lowered for the chip, the kernels
        ``ops.pallas_kernels.hc_pre`` (coefficients, every Sinkhorn round on
        registers, the read; under the scope ``hc.coef``) and ``hc_post``
        (the write-back where the streams lie; ``hc.mix``); where it is
        lowered for the CPU, ``ops.hyper_connection``, which is their
        definition (``by_platform``: nothing a caller sets chooses, and
        ``decode.hc.lowered`` counts which was built, once a half).  A hidden
        width that is not whole lane tiles (tiny blocks) is the definition's
        on every platform."""
        import jax
        from ...ops.pallas_kernels import by_platform, hc_post, hc_pre
        gain = p[f"l{i}_norm_{sub}"]
        if self.hc_mult == 1:
            for part in f(_rms(h, gain, self.eps)):
                h = h + part
            return h
        lead, n = h.shape[:-2], self.hc_mult
        rounds = {"iters": self.hc_iters, "eps": self.hc_eps,
                  "clamp": self.hc_clamp}

        def read(h, phi_t, a, b, _live):
            h_pre, h_post, h_res = _hc.hc_coefficients(
                h, {"phi": phi_t.T, "a": a, "b": b}, **rounds)
            return _hc.hc_read(h, h_pre), _hc.coef_tile(h_pre, h_post, h_res)

        def read_kernel(*args):
            with jax.named_scope("hc.coef"):
                return hc_pre(*args, **rounds)

        def write(h, coef, y):
            _, h_post, h_res = _hc.coef_parts(coef, lead, n)
            return _hc.hc_write(h, h_res, h_post, y)

        def write_kernel(*args):
            with jax.named_scope("hc.mix"):
                return hc_post(*args)

        if self.units % 128:
            lowered = lambda *args, kernel, plain: plain(*args)
        else:
            lowered = functools.partial(by_platform, "decode.hc.lowered",
                                        tokens=math.prod(lead))
        u, coef = lowered(
            h, *(p[f"l{i}_hc_{sub}_{k}"] for k in ("phi", "a", "b")), live,
            kernel=read_kernel, plain=read)
        if resid is not None:
            resid.append(_hc.sinkhorn_residual(
                _hc.coef_parts(coef, lead, n)[2]))
        return lowered(h, coef, sum(f(_rms(u, gain, self.eps))),
                       kernel=write_kernel, plain=write)

    def _ffn(self, p, i, h, valid, counts, resid=None):
        """``h + FFN(RMSNorm(h))`` over flat rows ``h (T, U)`` (streams ``(T,
        n, U)`` with ``hc_mult > 1``): the feed-forward sublayer whole.
        ``perf/tools/route_flips.py`` follows the program through it."""
        return self._sublayer(
            p, i, "ffn", h,
            lambda m: self._ffn_parts(p, i, m, valid, counts), resid, valid)

    def _ffn_parts(self, p, i, m, valid, counts):
        """The parts of ``FFN(m)`` over flat normed rows ``m (T, U)``: the
        dense SwiGLU, or the held experts' share and the shared expert."""
        import jax
        from ...parallel.moe import routed_expert_share
        pre = f"l{i}_"
        if i < self.first_dense:
            with jax.named_scope("ffn.dense"):
                return (_swiglu(m, p[pre + "wg"], p[pre + "wu"],
                                p[pre + "wd"]),)
        y, rows, n_assign = routed_expert_share(
            m, p[pre + "router"], p[pre + "exp_wg"], p[pre + "exp_wu"],
            p[pre + "exp_wd"], self.held, top_k=self.top_k,
            n_group=self.n_group, topk_group=self.topk_group,
            scale=self.routed_scale, valid=valid,
            select_bias=p[pre + "select_bias"] if self.select_bias
            else None)
        counts.append((rows, n_assign))
        with jax.named_scope("moe.shared"):
            shared = _swiglu(m, p[pre + "sh_wg"], p[pre + "sh_wu"],
                             p[pre + "sh_wd"])
        return y, shared

    def _streams(self, h):
        """The residual path's start: the embedding ``h (..., U)`` itself,
        or ``hc_mult`` copies of it ``(..., n, U)``."""
        import jax.numpy as jnp
        if self.hc_mult == 1:
            return h
        return jnp.broadcast_to(h[..., None, :],
                                h.shape[:-1] + (self.hc_mult, h.shape[-1]))

    def _merged(self, h):
        """The residual path's end: the streams summed."""
        return h if self.hc_mult == 1 else h.sum(-2)

    def _attend_whole(self, scores, p, i, a, positions, causal):
        """Expanded attention over a whole sequence ``a (B, S, U)`` with
        ``scores(q_nope (B, S, H, nope), q_rope (B, S, H, rope), kv (B, S,
        H, nope + v) float32, k_r (B, S, rope), causal) -> (B, S, H v)`` in
        the middle: the queries, the latent rows in the cache dtype, the
        per-head keys and values made from those (stored-precision) rows,
        and ``W_o`` behind it."""
        dt = self.dtype
        q_nope, q_rope = self._queries(p, i, a, positions)
        rows = self._latent_row(p, i, a, positions).astype(dt)
        ckv = rows[..., :self.kv_lora_rank]
        kr = rows[..., self.kv_lora_rank:self.row_width]
        kv = _einsum("bsc,chd->bshd", ckv, self._wkvb(p, i), dt)
        return _dot(scores(q_nope, q_rope, kv, kr, causal),
                    p[f"l{i}_wo"]), rows

    def _scores_chain(self, q_nope, q_rope, kv, kr, causal):
        """The definition of the expanded attention's middle under the mask
        ``causal (S, S)``: every head's ``(S, S)`` float32 scores, one
        softmax, ``p . v``."""
        import jax
        import jax.numpy as jnp
        dt = self.dtype
        k_nope, v = kv[..., :self.nope_dim], kv[..., self.nope_dim:]
        s = (_einsum("bqhd,bkhd->bhqk", q_nope, k_nope, dt)
             + _einsum("bqhr,bkr->bhqk", q_rope, kr, dt)) * self._scale
        s = jnp.where(causal[None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = _einsum("bhqk,bkhd->bqhd", pr, v, dt)
        return o.reshape(o.shape[:2] + (-1,))

    def _scores_lowered(self, q_nope, q_rope, kv, kr, causal):
        """:meth:`_scores_chain` as the prefill program runs it: where the
        program is lowered for the chip ONE kernel
        (``ops.pallas_kernels.mla_prefill_attention``: by blocks, the causal
        order its only mask, no ``(H, S, S)`` array; ``causal`` is then not
        read), where it is lowered for the CPU the definition
        (``by_platform``: nothing a caller sets chooses, and
        ``decode.mla.prefill.lowered`` counts which was built, once a
        layer).  Widths that are not whole lane tiles, an odd number of
        heads or a length that is not whole blocks (tiny blocks) are the
        definition's on every platform."""
        from ...ops.pallas_kernels import by_platform, mla_prefill_attention
        S = q_nope.shape[1]
        if self.nope_dim % 128 or self.v_dim % 128 or \
                2 * self.rope_dim % 128 or self.num_heads % 2 or S % 128:
            return self._scores_chain(q_nope, q_rope, kv, kr, causal)

        def kernel(q_nope, q_rope, kv, kr, _causal):
            return mla_prefill_attention(
                q_nope, q_rope, kv.astype(self.dtype), kr,
                scale=self._scale).astype("float32")

        return by_platform("decode.mla.prefill.lowered", q_nope, q_rope, kv,
                           kr, causal, kernel=kernel,
                           plain=self._scores_chain, tokens=S)

    def attend_expanded(self, p, i, a, positions, causal):
        """Expanded attention over a whole sequence ``a (B, S, U)``:
        per-head keys and values from the (stored-precision) latent rows.
        Returns ``(attention output (B, S, U) float32, rows (B, S,
        pool_width) in the cache dtype)``.  The definition: what a prefill
        program lowered for the CPU runs, and what the kernel of one lowered
        for the chip is held to."""
        return self._attend_whole(self._scores_chain, p, i, a, positions,
                                  causal)

    def _fold(self, p, i, a, positions):
        """One query per row ``a (B, U)`` in the latent rows' own space,
        ``(B, H, pool_width)`` float32: ``W_kvb``'s key half folded into the
        unrotated part, the rotated part beside it, zeros over the row's
        padding."""
        import jax.numpy as jnp
        q_nope, q_rope = self._queries(p, i, a, positions)
        q_lat = _einsum("bhd,chd->bhc", q_nope,
                        self._wkvb(p, i)[..., :self.nope_dim], self.dtype)
        pad = jnp.zeros(q_rope.shape[:-1]
                        + (self.pool_width - self.row_width,), q_rope.dtype)
        return jnp.concatenate([q_lat, q_rope, pad], axis=-1)

    def context_absorbed(self, q, rows, mask):
        """The folded queries' attention over latent ``rows (B, L,
        pool_width)``, keys and values at once (``mask (B, 1, L)`` marks the
        live ones): ``(B, H, pool_width)`` float32.  What
        ``PageFormat.attend`` computes over the pool's pages."""
        import jax
        import jax.numpy as jnp
        s = _einsum("bhk,blk->bhl", q, rows, self.dtype) * self._scale
        pr = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return _einsum("bhl,blk->bhk", pr, rows, self.dtype)

    def _unfold(self, p, i, ctx):
        """The attention output ``(B, U)`` of a context ``(B, H,
        pool_width)``: ``W_kvb``'s value half applied to its ``c_kv``
        columns, then ``W_o``."""
        o = _einsum("bhc,chd->bhd", ctx[..., :self.kv_lora_rank],
                    self._wkvb(p, i)[..., self.nope_dim:], self.dtype)
        return _dot(o.reshape(o.shape[0], -1), p[f"l{i}_wo"])

    def attend_absorbed(self, p, i, a, positions, rows, mask):
        """Absorbed attention of one query per row ``a (B, U)`` over latent
        ``rows (B, L, pool_width)`` (``mask (B, L)`` marks the live ones):
        ``W_kvb``'s key half is folded into the query and its value half is
        applied to the context.  Returns the attention output ``(B, U)``."""
        q = self._fold(p, i, a, positions)
        return self._unfold(p, i, self.context_absorbed(q, rows,
                                                        mask[:, None]))

    def prefill_math(self, p, tokens, lengths):
        """Pure prefill: ``(last_logits, rows)`` — see the class docstring.
        Padded positions are routed to no expert."""
        import jax
        import jax.numpy as jnp
        B, S = tokens.shape
        h = self._streams(p["embed"][tokens].astype(jnp.float32))
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        causal = jnp.tril(jnp.ones((S, S), bool))
        valid = (pos < lengths[:, None]).reshape(-1)
        out_rows, counts = [], []
        for i in range(self.num_layers):
            def attend(a, i=i):
                with jax.named_scope("mla.attend"):
                    o, rows = self._attend_whole(self._scores_lowered, p, i,
                                                 a, pos, causal)
                out_rows.append(rows)
                return (o,)

            h = self._sublayer(p, i, "attn", h, attend)
            h = self._ffn(p, i, h.reshape((B * S,) + h.shape[2:]), valid,
                          counts).reshape(h.shape)
        h = self._merged(h)
        last = _rms(h[jnp.arange(B), lengths - 1], p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(last, p["head"])
        return logits, jnp.stack(out_rows)

    def step_program(self, p, tokens, positions, tables, pools, pages):
        """Pure fused decode step, one token a row: writes each row's latent
        row into its page and attends absorbed over the row's paged context
        through ``pages.attend`` (the cache's ``PageFormat``: the live pages
        where they lie on the chip, the gathered context on the CPU).  Rows
        whose table starts with the trash page are padding and are routed
        to no expert.  Returns ``(logits (B, vocab), pools, (moe_rows
        (expert layers, held + 1) int32,))``: per expert layer the rows each
        held expert received, then the assignments made over all experts.
        With ``hc_mult > 1`` the extras end with one more number, the bits
        of a float32: how far the step's worst ``Hres`` of a live row lies
        from doubly stochastic (``hyper_connection.sinkhorn_residual``)."""
        import jax
        import jax.numpy as jnp
        page_size = pages.page_size
        h = self._streams(p["embed"][tokens].astype(jnp.float32))
        wp = jnp.take_along_axis(tables, (positions // page_size)[:, None],
                                 axis=1)[:, 0]
        woff = positions % page_size
        valid = tables[:, 0] != 0
        counts, resid = [], []
        for i in range(self.num_layers):
            def attend(a, i=i):
                nonlocal pools
                with jax.named_scope("mla.attend"):
                    row = self._latent_row(p, i, a, positions)
                    pools = pages.write(pools, i, wp, woff, (row,))
                    q = self._fold(p, i, a, positions)
                    ctx = pages.attend(
                        pools, i, tables, positions, q,
                        functools.partial(self.context_absorbed, q),
                        scale=self._scale)
                    return (self._unfold(p, i, ctx),)

            h = self._sublayer(p, i, "attn", h, attend, resid, valid)
            h = self._ffn(p, i, h, valid, counts, resid)
        hf = _rms(self._merged(h), p["norm_f"], self.eps)
        with jax.named_scope("head"):
            logits = _dot(hf, p["head"])
        extras = (moe_rows_of(counts, len(self.held)),)
        if resid:
            # the largest over the live rows and the sublayers, as its bits:
            # the runtime's vector of counts is int32
            worst = jnp.where(valid[None], jnp.stack(resid), 0.0).max()
            extras += (jax.lax.bitcast_convert_type(worst, jnp.int32),)
        return logits, pools, extras

    def commit_program(self, rows, lengths, tables, pools, pages):
        """Store the prefill's ``rows (layers, B, S, pool_width)`` in the
        pool at the pages ``tables`` names, a layer at a time."""
        dest_page, dest_off = commit_destinations(
            rows.shape[2], lengths, tables, pages.page_size)
        for i in range(self.num_layers):
            pools = pages.write(pools, i, dest_page, dest_off, (rows[i],))
        return pools

    sample_math = staticmethod(sample_math)

    def record_step_extras(self, extras, model):
        """Telemetry from one step's extras (the program's vector of counts,
        flat): the ``decode.moe.*`` counters ``docs/telemetry.md`` lists
        and, with ``hc_mult > 1``, the gauges ``decode.hc.streams`` and
        ``decode.hc.sinkhorn_residual``."""
        extras = np.asarray(extras)
        if self.hc_mult > 1:
            _tel.gauge("decode.hc.streams", self.hc_mult)
            _tel.gauge("decode.hc.sinkhorn_residual",
                       float(extras[-1:].view(np.float32)[0]))
            extras = extras[:-1]
        record_moe_rows(extras.reshape(-1, len(self.held) + 1), model)

    # ------------------------------------------------------- gluon frontend
    def hybrid_forward(self, F, tokens, lengths, **params):
        if not isinstance(tokens, NDArray) and not hasattr(tokens, "_data"):
            raise NotImplementedError(
                "LatentMoELM has no symbolic frontend (export is not "
                "supported); the decode runtime compiles it through "
                "compile_grid / the CachedOp path instead")
        leaves = [params[n] for n in self._param_order]

        def pure(tok, ln_, *leaf_vals):
            # the gluon path hands the parameters as registered: the
            # prefill turns its phis itself, 9 MB a sublayer beside the
            # hundreds its tokens' streams move
            return self.prefill_math(self._params_dict(
                self._served(n, v) for n, v in zip(self._param_order,
                                                   leaf_vals)), tok, ln_)

        return tuple(invoke_fn(pure, [tokens, lengths] + leaves,
                               op_name="latent_moe_prefill"))
