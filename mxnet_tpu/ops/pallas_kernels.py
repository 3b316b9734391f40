"""Pallas TPU kernels for the hot ops.

The reference's answer to "the framework op isn't fast enough" was
hand-written CUDA (``src/operator/*.cu``) or NVRTC runtime compilation
(``mx.rtc``, src/common/rtc.cc); the TPU-native answer is Pallas.  First
resident kernel: **flash attention** — blockwise attention that never
writes a T×T float32 tensor to HBM, in either direction (the memory story
behind the sequence-parallel design, SURVEY.md §5.7).

``flash_attention`` is differentiable and both passes are kernels, on the
grid (group of heads, q blocks, k blocks), one (BQ, BK) score tile a head a
step:

- forward: online softmax in float32 with the running max, denominator
  and accumulator in VMEM scratch; returns the output and the rows'
  log-sum-exp, lane-dense as (batch*head, 1, T);
- backward, one kernel: each step recomputes its tile of probabilities from
  q, k and the saved log-sum-exp, takes ``delta = rowsum(dO * O)`` from the
  saved output (exact with dropout, O being the dropped probabilities
  times V), accumulates dQ in scratch and dK, dV into whole-sequence
  float32 output blocks that stay in VMEM while a group's q blocks go by.
  Those two blocks are the only thing whose size grows with T (2 KiB of
  VMEM a token at 128 lanes): the backward asks the compiler for the VMEM
  they take, and a v5e core's 128 MiB refuses it past some 57,000 tokens;
  the forward has no such limit.  The key gradients of a head are
  recentred over its keys afterwards (``_fa_backward`` says why).

The kernels read (B, T, H*D) arrays, heads side by side as a layer's
projections leave them, and a block is as many heads as fill the 128 lanes
(``_layout``): the (B, H, T, D) of the public signature costs a layer no
copy.

Two optional operands, present or absent: ``kv_mask`` (B, T), 1 = valid
key, which enters as an additive bias on the score tile; and ``keep``
(B*H, T, T) int8, dropout's keep-mask on the probabilities, made by the
caller (``ops/__init__.py`` draws it with ``jax.random.bernoulli``) and
read a tile a step.  The row sum is taken from the undropped
probabilities; the keep-mask and ``1 / (1 - rate)`` apply to what enters
probabilities x values.

Products are at XLA's default precision for the backend: on the TPU they
take bfloat16 inputs and accumulate in float32, which is what XLA does to
float32 operands there; under the interpreter on the CPU they are float32,
as XLA's are.  ``jax.default_matmul_precision`` overrides both ways
("highest" for an exactness check on the chip, "bfloat16" to see the
chip's rounding on the CPU).  Softmax statistics, outputs and gradients
are float32.

Second resident kernel: **the decode step's state-space recurrence on the
rows' states where they lie** (``ssm_step_slots``): the whole state pool of
a cache is an operand aliased to the result, the layer and each batch row's
state row are prefetched scalars that pick the block a grid step moves, and
each LIVE row's state crosses memory once each way: nothing for a padded
row or an idle slot, where XLA's forms pass two to three times over every
slot of the layer.  float32 on the vector unit, no product on the MXU.
``by_platform`` is how its caller gets the kernel on the chip and the
definition on the CPU, and a count of which was built.  ``kda_step_slots``
is the same kernel for the gated delta rule's matrix state (``ops.
delta_rule``: a decay a key channel, a rank-one correction, 64 KB a head).

Third resident kernel: **the decode step's grouped-query attention over the
rows' pages where they lie** (``paged_attention``): the cache's K and V page
pools stay whole in device memory, the layer, the page tables and the rows'
positions are prefetched scalars, and each LIVE row's LIVE pages cross
memory once, a page a DMA, in double-buffered blocks under an online
softmax: nothing for a padded row or for a reserved page that holds no
token yet, where the gathering form copies every reserved page of every row
of the program, and the compiler copies them once more.  Products in the
pools' dtype on the MXU, float32 accumulation and softmax.
``serving.decode.kv_format.PageFormat.attend`` is its one caller.

Fourth resident kernel: **the same walk over a latent-attention block's ONE
pool** (``paged_latent_attention``), whose row is keys and values at once
and shared by every head: a live page crosses memory once and the block in
VMEM is both operands of the absorbed form (scores ``q . block^T``, context
``p . block``), under a softmax scale the caller gives.  A body and a jitted
call of its own behind the same door; ``paged_attention``'s are untouched.
"""
from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "ssm_step_slots", "kda_step_slots",
           "paged_attention", "paged_latent_attention", "by_platform"]

_NEG = -1e30

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _products(interpret):
    """(operand dtype, precision) of the kernels' matrix products: XLA's
    default for float32 operands on the platform the kernels run on (one
    bfloat16 pass on the TPU, float32 under the interpreter on the CPU),
    unless ``jax.default_matmul_precision`` names one."""
    named = jax.config.jax_default_matmul_precision
    if named == "bfloat16" or (named in (None, "default") and not interpret):
        return jnp.bfloat16, None
    return jnp.float32, jax.lax.Precision.HIGHEST


def _eye(n):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)


def _col_to_row(col):
    """(n, 1) -> (1, n) by a masked reduction: statistics live as columns
    beside a score tile and as lane-dense rows in HBM (an (.., T, 1) array
    is padded to 128 lanes there)."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


def _row_to_col(row):
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _scores(q, k, bias_ref, qi, ki, causal, precision):
    """The (BQ, BK) float32 score tile of step (qi, ki): q k^T, the key
    bias, the causal mask."""
    bq, bk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, _NT, precision=precision,
                            preferred_element_type=jnp.float32)
    if bias_ref is not None:
        s = s + bias_ref[0]
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG)
    return s


def _lanes(width, pack):
    """One lane mask (1, width) a packed head, or ``[None]`` for one head a
    block: head ``a`` of a block owns lanes ``a * d .. (a + 1) * d``."""
    if pack == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [lane // (width // pack) == a for a in range(pack)]


def _own(sel, new, old):
    """``new`` on a head's own lanes, ``old`` on the others."""
    return new if sel is None else jnp.where(sel, new, old)


def _when_live(qi, ki, bq, bk, causal, tile):
    """Run ``tile`` unless every key of step (qi, ki) lies past the
    diagonal of every query of the block."""
    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(tile)
    else:
        tile()


def _fa_kernel(q_ref, k_ref, v_ref, *rest, causal, scale, keep_prob, biased,
               dropped, pack, products):
    """One (group of ``pack`` heads, q block, k block) step of the forward.

    Blocks: q, o (1, BQ, pack*D); k, v (1, BK, pack*D); bias (1, 1, BK)
    float32 (0, -1e30 on a masked key, -inf on the padding); keep (pack,
    BQ, BK) int8; lse (pack, 1, BQ).  Scratch: m, l (pack, BQ, 1) and acc
    (BQ, pack*D), float32.
    """
    rest = list(rest)
    bias_ref = rest.pop(0) if biased else None
    keep_ref = rest.pop(0) if dropped else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    mxu, precision = products
    lanes = _lanes(q_ref.shape[2], pack)

    @pl.when(ki == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def tile():
        q = q_ref[0].astype(jnp.float32) * scale
        k, v = k_ref[0].astype(mxu), v_ref[0].astype(mxu)
        acc = acc_sc[...]
        for a, sel in enumerate(lanes):
            s = _scores(_own(sel, q, 0.0).astype(mxu), k, bias_ref, qi, ki,
                        causal, precision)
            m = m_sc[a]
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - new_m)
            corr = jnp.exp(m - new_m)
            l_sc[a] = l_sc[a] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_sc[a] = new_m
            if dropped:
                p = p * keep_ref[a].astype(jnp.float32)
            pv = jnp.dot(p.astype(mxu), v, precision=precision,
                         preferred_element_type=jnp.float32)
            acc = _own(sel, acc * corr + pv, acc)
        acc_sc[...] = acc

    _when_live(qi, ki, bq, bk, causal, tile)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        out = acc_sc[...]
        for a, sel in enumerate(lanes):
            l = l_sc[a]
            out = _own(sel, acc_sc[...] / (l * keep_prob), out)
            lse_ref[a] = _col_to_row(m_sc[a] + jnp.log(l))
        o_ref[0] = out.astype(o_ref.dtype)


def _fa_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                   causal, scale, keep_prob, biased, dropped, pack, products):
    """One (group of ``pack`` heads, q block, k block) step of the backward.

    Blocks as the forward's, with do (1, BQ, pack*D); outputs dq (1, BQ,
    pack*D) and dk, dv (1, T, pack*D) float32, whole-sequence and resident
    while the step's group of heads lasts.  Scratch: dq (BQ, pack*D), lse
    and delta (pack, BQ, 1).
    """
    rest = list(rest)
    bias_ref = rest.pop(0) if biased else None
    keep_ref = rest.pop(0) if dropped else None
    dq_ref, dk_ref, dv_ref, dq_sc, lse_sc, delta_sc = rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    mxu, precision = products
    dot = functools.partial(jax.lax.dot_general, precision=precision,
                            preferred_element_type=jnp.float32)
    lanes = _lanes(q_ref.shape[2], pack)

    @pl.when((qi == 0) & (ki == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    @pl.when(ki == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)
        odo = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
        for a, sel in enumerate(lanes):
            lse_sc[a] = _row_to_col(lse_ref[a])
            delta_sc[a] = jnp.sum(_own(sel, odo, 0.0), axis=-1,
                                  keepdims=True)

    def tile():
        q = q_ref[0].astype(jnp.float32) * scale
        # dO / keep once on the (BQ, pack*D) block, not on the tiles
        do = do_ref[0].astype(jnp.float32) * (1.0 / keep_prob)
        k, v = k_ref[0].astype(mxu), v_ref[0].astype(mxu)
        q_all, do_all = q.astype(mxu), do.astype(mxu)
        dq = dk = dv = 0.0
        for a, sel in enumerate(lanes):
            s = _scores(_own(sel, q, 0.0).astype(mxu), k, bias_ref, qi, ki,
                        causal, precision)
            p = jnp.exp(s - lse_sc[a])
            dp = dot(_own(sel, do, 0.0).astype(mxu), v, _NT)
            pd = p
            if dropped:
                keep = keep_ref[a].astype(jnp.float32)
                pd, dp = p * keep, dp * keep
            ds = (p * (dp - delta_sc[a])).astype(mxu)
            # each product is right on the head's own lanes only
            dv = _own(sel, dot(pd.astype(mxu), do_all, _TN), dv)
            dk = _own(sel, dot(ds, q_all, _TN), dk)
            dq = _own(sel, dot(ds, k, (((1,), (0,)), ((), ()))), dq)
        rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)
        dv_ref[0, rows, :] += dv
        dk_ref[0, rows, :] += dk
        dq_sc[...] += dq

    _when_live(qi, ki, bq, bk, causal, tile)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _blocks(t, block_q, block_k):
    """(padded T, BQ, BK): T goes to the next multiple of 128 and the
    blocks are the largest of 256 | 128 query rows and 512 | 256 | 128 keys
    that divide it, unless the caller names them (T is then padded to
    their larger one, which the smaller has to divide)."""
    if block_q and block_k:
        block = max(block_q, block_k)
        if block % min(block_q, block_k):
            raise ValueError(f"flash_attention: blocks {block_q} and "
                             f"{block_k} do not divide one another")
        return t + (-t) % block, block_q, block_k
    tp = t + (-t) % 128
    pick = lambda sizes: next(b for b in sizes if tp % b == 0)
    return tp, block_q or pick((256, 128)), block_k or pick((512, 256, 128))


def _layout(h, d):
    """(pack, cols): heads a block, and blocks side by side in a row of
    the arrays the kernels read (0: a head a row, see below).

    A block holds as many heads as fill the 128 lanes, where the head
    count divides (64-wide heads go in pairs): an (.., T, 64) float32 array
    is padded to 128 lanes in HBM, so a head a block would move and hold
    twice the bytes.  Where a block is whole lanes wide the arrays are (B,
    T, H*D), heads side by side as the projections leave them, and a block
    is ``pack`` heads of a row: a caller whose (B, H, T, D) is a transpose
    of that (an attention layer's is) pays no copy, XLA folds the two
    transposes.  Else they are (B*H, T, D), a head a block."""
    pack = max(1, 128 // d)
    if d * pack != 128 or h % pack:
        pack = 1
    return pack, h // pack if (pack * d) % 128 == 0 else 0


def _pack(x, tp):
    """(B, H, T, D) -> the kernels' (rows, Tp, cols * width), zero rows
    past T."""
    b, h, t, d = x.shape
    if _layout(h, d)[1]:
        x = x.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    else:
        x = x.reshape(b * h, t, d)
    return jnp.pad(x, [(0, 0), (0, tp - t), (0, 0)]) if tp > t else x


def _unpack(x, like):
    """The inverse, to ``like``'s (B, H, T, D) and dtype."""
    b, h, t, d = like.shape
    x = x[:, :t].astype(like.dtype)
    if _layout(h, d)[1]:
        return x.reshape(b, t, h, d).transpose(0, 2, 1, 3)
    return x.reshape(b, h, t, d)


def _operands(q, kv_mask, keep, tp):
    """The kernels' optional operands at the padded length: the key bias
    (rows, 1, Tp) float32, needed when a mask is passed or keys are padding
    (one row then serves every head), and the keep-mask, zero over the
    padding."""
    b, h, t, _ = q.shape
    bias = None
    if kv_mask is not None:
        bias = (1.0 - kv_mask.astype(jnp.float32).reshape(b, 1, t)) * _NEG
    elif tp > t:
        bias = jnp.zeros((1, 1, t), jnp.float32)
    if bias is not None and tp > t:
        bias = jnp.pad(bias, [(0, 0), (0, 0), (0, tp - t)],
                       constant_values=-jnp.inf)
    if keep is not None:
        if keep.shape != (b * h, t, t):
            raise ValueError(f"flash_attention: keep-mask {keep.shape} for "
                             f"{b} x {h} heads of {t} tokens")
        keep = keep.astype(jnp.int8)
        if tp > t:
            keep = jnp.pad(keep, [(0, 0), (0, tp - t), (0, tp - t)])
    return bias, keep


class _Static(typing.NamedTuple):
    """What a call fixes beside its arrays."""
    causal: bool
    scale: float
    block_q: typing.Optional[int]
    block_k: typing.Optional[int]
    interpret: typing.Optional[bool]
    rate: float


def _call(kernel, name, static, shape, operands, outs, scratch, semantics,
          bias, keep):
    """``pallas_call`` of a forward or backward kernel on the grid (group
    of heads, q block, k block) for attention of ``shape`` (B, H, T, D).
    ``operands`` are (array as ``_pack`` lays it, kind) with kind "q" (a q
    block a step), "k" (a k block a step) or "lse"; ``outs`` are (kind,
    dtype) of the same kinds, and "whole" for a resident whole-sequence
    block.  It is one jitted function of its arrays, so that the twelve
    layers of a model trace and lower each kernel once, not twelve times."""
    return _jitted_call(
        *(x for x, _ in operands), *(x for x in (bias, keep)
                                     if x is not None),
        kernel=kernel, name=name, static=static, shape=tuple(shape),
        kinds=tuple(kind for _, kind in operands),
        outs=tuple((kind, jnp.dtype(dtype)) for kind, dtype in outs),
        scratch=tuple(scratch), semantics=semantics,
        biased=bias is not None, dropped=keep is not None)


@functools.partial(jax.jit, static_argnames=(
    "kernel", "name", "static", "shape", "kinds", "outs", "scratch",
    "semantics", "biased", "dropped"))
def _jitted_call(*arrays, kernel, name, static, shape, kinds, outs, scratch,
                 semantics, biased, dropped):
    arrays = list(arrays)
    keep = arrays.pop() if dropped else None
    bias = arrays.pop() if biased else None
    operands = list(zip(arrays, kinds))
    causal, scale, block_q, block_k, interpret, rate = static
    b, h, t, d = shape
    tp, bq, bk = _blocks(t, block_q, block_k)
    pack, cols = _layout(h, d)
    cols = cols or 1
    width, groups = pack * d, b * h // pack
    spec = {
        "q": pl.BlockSpec((1, bq, width),
                          lambda i, j, kk: (i // cols, j, i % cols)),
        "k": pl.BlockSpec((1, bk, width),
                          lambda i, j, kk: (i // cols, kk, i % cols)),
        "lse": pl.BlockSpec((pack, 1, bq), lambda i, j, kk: (i, 0, j)),
        "whole": pl.BlockSpec((1, tp, width),
                              lambda i, j, kk: (i // cols, 0, i % cols))}
    arrays = [x for x, _ in operands]
    in_specs = [spec[kind] for _, kind in operands]
    if bias is not None:
        per_batch = bias.shape[0] > 1
        arrays.append(bias)
        in_specs.append(pl.BlockSpec(
            (1, 1, bk),
            lambda i, j, kk: (i * pack // h if per_batch else 0, 0, kk)))
    if keep is not None:
        arrays.append(keep)
        in_specs.append(pl.BlockSpec((pack, bq, bk),
                                     lambda i, j, kk: (i, j, kk)))
    rows = (groups // cols, tp, cols * width)
    out_shape = {"q": rows, "whole": rows, "lse": (b * h, 1, tp)}
    vmem = {"tile": (bq, width), "stat": (pack, bq, 1)}
    # whole-sequence blocks (the backward's dK, dV; float32, two buffers
    # each) outgrow Mosaic's default 16 MiB of scoped VMEM near 7,000
    # tokens: ask for what they take, which a v5e core's 128 MiB bounds
    resident = 8 * tp * width * [kind for kind, _ in outs].count("whole")
    limit = resident + (8 << 20) if resident > (6 << 20) else None
    def call(interpret):
        return pl.pallas_call(
            functools.partial(kernel, causal=causal, scale=scale,
                              keep_prob=1.0 - rate, biased=bias is not None,
                              dropped=keep is not None, pack=pack,
                              products=_products(interpret)),
            out_shape=tuple(jax.ShapeDtypeStruct(out_shape[kind], dtype)
                            for kind, dtype in outs),
            grid=(groups, tp // bq, tp // bk),
            in_specs=in_specs,
            out_specs=tuple(spec[kind] for kind, _ in outs),
            scratch_shapes=[pltpu.VMEM(vmem[kind], jnp.float32)
                            for kind in scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=limit),
            interpret=interpret, name=name)

    if interpret is not None:
        return call(interpret)(*arrays)
    # by where the arrays live, at lowering: a block initialised on the
    # host of a process that holds a chip runs its first forward there
    return jax.lax.platform_dependent(*arrays, cpu=call(True),
                                      default=call(False))


def _fa_forward(q, k, v, kv_mask, keep, static):
    """Output (B, H, T, D), and what the backward reads: q, k, v and the
    output as the kernels hold them (``_pack``), the rows' log-sum-exp
    (B*H, 1, Tp), the key bias and the padded keep-mask."""
    tp = _blocks(q.shape[2], static.block_q, static.block_k)[0]
    bias, keep = _operands(q, kv_mask, keep, tp)
    qf, kf, vf = (_pack(x, tp) for x in (q, k, v))
    of, lse = _call(
        _fa_kernel, "flash_attention_fwd", static, q.shape,
        [(qf, "q"), (kf, "k"), (vf, "k")],
        [("q", q.dtype), ("lse", jnp.float32)], ["stat", "stat", "tile"],
        ("parallel", "parallel", "arbitrary"), bias, keep)
    return _unpack(of, q), (qf, kf, vf, of, lse, bias, keep)


def _fa_backward(like, res, do, static):
    """(dq, dk, dv) as ``like`` = (q, k, v) shapes and dtypes."""
    qf, kf, vf, of, lse, bias, keep = res
    b, _, t, _ = like[0].shape
    tp = _blocks(t, static.block_q, static.block_k)[0]
    dq, dk, dv = _call(
        _fa_bwd_kernel, "flash_attention_bwd", static, like[0].shape,
        [(qf, "q"), (kf, "k"), (vf, "k"), (of, "q"),
         (_pack(do, tp), "q"), (lse, "lse")],
        [("q", qf.dtype), ("whole", jnp.float32), ("whole", jnp.float32)],
        ["tile", "stat", "stat"], ("parallel", "arbitrary", "arbitrary"),
        bias, keep)
    # Scores do not change when every key of a head moves by one vector,
    # so a head's key gradients sum to zero over its (unmasked) keys.  dS
    # rounded to bfloat16 keeps that only to a bfloat16's rounding, and the
    # remainder is all there is of the key bias's gradient: noise (0.015
    # where float32 leaves 2e-6, my chip run, PR 27) that Adam's
    # normalisation turns into full-size steps of that bias.  Take it out.
    total = dk.sum(axis=1, keepdims=True)
    if bias is None:
        dk = dk - total / tp
    else:
        live = (bias == 0.0).astype(jnp.float32).reshape(-1, tp, 1)
        share = live / jnp.maximum(live.sum(axis=1, keepdims=True), 1.0)
        if share.shape[0] > 1:      # a row a batch: one for each of dk's
            share = jnp.repeat(share, dk.shape[0] // b, axis=0)
        dk = dk - share * total
    return tuple(_unpack(g, x) for g, x in zip((dq, dk, dv), like))


def _reference(q, k, v, causal, scale, kv_mask=None, keep=None, rate=0.0):
    """The dense formula, (B, H, T, T) scores and all: what the tests and
    ``chip_smoke.py`` hold the kernels to."""
    b, h, t, _ = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if kv_mask is not None:
        s = s + (1.0 - kv_mask.astype(jnp.float32))[:, None, None, :] * _NEG
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), dtype=bool)), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = p * keep.reshape(b, h, t, t).astype(jnp.float32) / (1.0 - rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def _static(q, causal, scale, block_q, block_k, interpret, rate):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _Static(causal, scale, block_q, block_k, interpret, rate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 10))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, kv_mask=None, keep=None,
                    rate=0.0):
    """Blockwise attention, (B, H, T, D) → (B, H, T, D).

    ``kv_mask`` (B, T), 1 = valid key, and ``keep`` (B*H, T, T), dropout's
    keep-mask on the probabilities at ``rate``, are operands that are there
    or not.  ``interpret=None`` selects by the platform the call is lowered
    for: the pallas interpreter on the CPU (the tests; a block's first
    forward on the host), the compiled kernels everywhere else — a kernel
    the chip's compiler refuses raises, it is never swapped for the dense
    formula.  T is padded to the block size internally; 64-wide
    heads ride two a block, so that every block fills the 128 lanes.
    """
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   kv_mask, keep, rate)[0]


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret, kv_mask,
            keep, rate):
    out, res = _fa_forward(q, k, v, kv_mask, keep, _static(
        q, causal, scale, block_q, block_k, interpret, rate))
    # the gradients' (H, T, D) and dtypes, which the padded, packed
    # residuals no longer tell, ride as empty arrays
    like = tuple(jnp.zeros((0,) + x.shape[1:], x.dtype) for x in (q, k, v))
    return out, (like, res)


def _fa_bwd(causal, scale, block_q, block_k, interpret, rate, saved, g):
    like, res = saved
    like = tuple(jax.ShapeDtypeStruct(g.shape, x.dtype) for x in like)
    return _fa_backward(like, res, g, _static(
        like[0], causal, scale, block_q, block_k, interpret, rate)) + \
        (None, None)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# --------------------------------------------------------------------------
# second resident kernel: one token of the state-space recurrence on the
# rows' states where they lie in the cache's state pool


def _ssm_slots_kernel(layer_ref, named_ref, rows_ref, decay_ref, s_ref,
                      dtx_ref, b_ref, c_ref, o_ref, y_ref, *, heads,
                      per_group):
    """One batch row's whole ``(H, P, N)`` state of the layer a grid step:
    ``S <- decay S + dt x (outer) B`` and ``y = S C``, a head at a time on
    the vector unit, P on the sublanes and N on the lanes.  ``dt x`` and
    ``y`` come and go with P on the sublanes too (``(P, H)``, a head a
    lane), so that a head's column broadcasts along the lanes and a lane
    reduction lands in it: nothing is laid out anew.  A padded row (state
    row 0) does nothing: it names the block a live row beside it names
    (``_ssm_slots_call``), so nothing is fetched or written back for it,
    and its ``y`` is zeros (finite, whatever the buffer held)."""
    del layer_ref
    i = pl.program_id(0)

    @pl.when(rows_ref[i] != 0)
    def _():
        for h in range(heads):
            g = h // per_group
            state = decay_ref[i * heads + h] * s_ref[0, 0, h] \
                + dtx_ref[0, :, h:h + 1] * b_ref[0, g:g + 1, :]
            o_ref[0, 0, h] = state
            y_ref[0, :, h:h + 1] = jnp.sum(state * c_ref[0, g:g + 1, :],
                                           axis=-1, keepdims=True)

    @pl.when(rows_ref[i] == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    # no live row at all (a warm-up drive): every step names the trash row,
    # and what is written back at the end is what was read
    @pl.when(jnp.logical_and(named_ref[0] == 0, i == 0))
    def _():
        o_ref[...] = s_ref[...]


def _rows_named(rows):
    """The state row each grid step of a slots kernel NAMES, from the batch
    rows' state rows ``rows (b,)`` (0: a padded row).  The pipeline fetches
    a block when a step names another than the step before it, and writes
    one back when the step after it names another; so a padded row names
    what the nearest live row before it names (the first live row, for
    padded rows in front of it) and costs an empty grid step.  With no live
    row every step names row 0, the trash row."""
    live = rows != 0
    before = jax.lax.cummax(jnp.where(live, jnp.arange(rows.shape[0]), -1))
    return rows[jnp.where(before >= 0, before, jnp.argmax(live))]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_slots_call(pool, layer, rows, decay, dtx, B, C, *, interpret):
    """``pallas_call`` on the grid (batch rows,) with the layer and the
    rows' state rows as prefetched scalars and the WHOLE pool aliased to
    its output.  One jitted function of its arrays, the layer among them:
    every Mamba layer of every step program lowers this once and the chip
    compiles one kernel body.

    The block is a row's state of one layer (2 MB at Nemotron-3-Nano's 64
    heads of 64 x 128): on the chip 2.59 ms for 23 layers at 14 live rows
    of 32 where blocks of an eighth of it took 5.96, and a kernel that only
    copies the blocks 2.26 (my chip runs, PR 31).  In, out and their second
    buffers are four blocks of the core's memory; eight blocks' bytes and 8
    MB are asked for.

    The pipeline fetches a block when a step names another than the step
    before it, and writes one back when the step after it names another.
    So a padded row names what a live row beside it names, the nearest one
    before it (the first live row, for padded rows in front of it): it
    costs an empty grid step.  With no live row every step names the trash
    row, which is copied through."""
    b, p, heads = dtx.shape
    groups, n = B.shape[1:]
    named = _rows_named(rows)

    state = pl.BlockSpec((1, 1, heads, p, n),
                         lambda i, layer, named, rows:
                         (layer[0], named[i], 0, 0, 0))
    by_row = lambda i, *_: (i, 0, 0)
    return pl.pallas_call(
        functools.partial(_ssm_slots_kernel, heads=heads,
                          per_group=heads // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state,
                      pl.BlockSpec((1, p, heads), by_row),
                      pl.BlockSpec((1, groups, n), by_row),
                      pl.BlockSpec((1, groups, n), by_row)],
            out_specs=(state, pl.BlockSpec((1, p, heads), by_row))),
        out_shape=(jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(dtx.shape, jnp.float32)),
        # operand 4, counting the prefetched scalars: the pool
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=8 * heads * p * n * 4 + (8 << 20)),
        interpret=interpret, name="ssm_step_slots",
    )(layer, named, rows, decay, pool, dtx, B, C)


def ssm_step_slots(pool, layer, rows, x, dt, A, B, C, D, *, interpret=False):
    """One token of the Mamba-2 recurrence (``ops.ssm.ssm_step``, which is
    its definition) for batch rows whose states lie in a state pool ``pool
    (layers, state rows, H, P, N)`` float32, at ``pool[layer, rows[i]]``:
    returns ``(pool, y (b, H, P))`` with those rows' states advanced where
    they lie.  ``x (b, H, P)``, ``dt (b, H)`` (softplus-ed), ``A``, ``D
    (H,)``, ``B``, ``C (b, G, N)``; ``layer`` a scalar (traced: one kernel
    for every layer), ``rows (b,)`` int32, distinct but for 0, which says a
    padded row.

    Each live row's state crosses memory once each way; a padded row,
    wherever it stands in the batch, moves nothing and its ``y`` is ``D x``
    alone (what it reads of a state is zero: for the caller to ignore);
    state row 0 (the trash row), every state row no batch row names and
    every other layer keep their bits.  The decay, ``dt x`` and the sum
    over the state are float32 on the vector unit, as ``ssm_step``'s: on
    the chip the two agree to the last bit (my chip run, PR 31)."""
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    pool, y = _ssm_slots_call(
        pool, jnp.asarray(layer, jnp.int32).reshape(1),
        rows.astype(jnp.int32), jnp.exp(dt * A).reshape(-1),
        jnp.swapaxes(dt[..., None] * x, 1, 2), B.astype(jnp.float32),
        C.astype(jnp.float32), interpret=interpret)
    return pool, jnp.swapaxes(y, 1, 2) + D[:, None] * x


# --------------------------------------------------------------------------
# the same for the gated delta rule: one token of the recurrence on the
# rows' matrix states where they lie in the cache's state pool


def _kda_slots_kernel(layer_ref, named_ref, rows_ref, beta_ref, s_ref,
                      a_ref, k_ref, q_ref, v_ref, o_ref, y_ref, *, heads):
    """One batch row's whole ``(H, dk, dv)`` state of the layer a grid
    step, a head at a time on the vector unit with ``dk`` on the sublanes
    and ``dv`` on the lanes: ``S <- a S``, ``u = beta (v - S^T k)``, ``S <-
    S + k u^T``, ``y = S^T q``.  The decay ``a``, ``k`` and ``q`` come with
    ``dk`` on the sublanes (``(dk, H)``, a head a lane) so that a head's
    column broadcasts along the lanes; ``v`` and ``y`` are rows of lanes
    (``(H, dv)``) and a reduction over the sublanes lands in one.  A padded
    row (state row 0) does nothing, as in ``_ssm_slots_kernel``."""
    del layer_ref
    i = pl.program_id(0)

    @pl.when(rows_ref[i] != 0)
    def _():
        for h in range(heads):
            k = k_ref[0, :, h:h + 1]
            state = a_ref[0, :, h:h + 1] * s_ref[0, 0, h]
            u = beta_ref[i * heads + h] * (
                v_ref[0, h:h + 1, :]
                - jnp.sum(state * k, axis=0, keepdims=True))
            state = state + k * u
            o_ref[0, 0, h] = state
            y_ref[0, h:h + 1, :] = jnp.sum(state * q_ref[0, :, h:h + 1],
                                           axis=0, keepdims=True)

    @pl.when(rows_ref[i] == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    # no live row at all (a warm-up drive): every step names the trash row,
    # and what is written back at the end is what was read
    @pl.when(jnp.logical_and(named_ref[0] == 0, i == 0))
    def _():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_slots_call(pool, layer, rows, beta, decay, k, q, v, *, interpret):
    """``pallas_call`` on the grid (batch rows,), as ``_ssm_slots_call``:
    the layer and the rows' state rows are prefetched scalars, the WHOLE
    pool is aliased to its output, and the block is a row's state of one
    layer in whole heads (64 KB a head, 4 MB at Solar-Open2's 64 heads of
    128 x 128; in, out and their second buffers are four blocks).  One
    jitted function of its arrays, the layer among them: every KDA layer of
    every step program lowers this once."""
    b, dk, heads = k.shape
    dv = v.shape[-1]
    state = pl.BlockSpec((1, 1, heads, dk, dv),
                         lambda i, layer, named, rows:
                         (layer[0], named[i], 0, 0, 0))
    by_row = lambda i, *_: (i, 0, 0)
    column, lanes = pl.BlockSpec((1, dk, heads), by_row), \
        pl.BlockSpec((1, heads, dv), by_row)
    return pl.pallas_call(
        functools.partial(_kda_slots_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state, column,
                      column, column, lanes],
            out_specs=(state, lanes)),
        out_shape=(jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)),
        # operand 4, counting the prefetched scalars: the pool
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=8 * heads * dk * dv * 4 + (8 << 20)),
        interpret=interpret, name="kda_step_slots",
    )(layer, _rows_named(rows), rows, beta, pool, decay, k, q, v)


def kda_step_slots(pool, layer, rows, q, k, v, g, beta, *, interpret=False):
    """One token of the gated delta rule (``ops.delta_rule.
    delta_rule_step``, which is its definition) for batch rows whose states
    lie in a state pool ``pool (layers, state rows, H, dk, dv)`` float32,
    at ``pool[layer, rows[i]]``: returns ``(pool, o (b, H, dv))`` with those
    rows' states advanced where they lie.  ``q``, ``k``, ``g (b, H, dk)``
    (``g <= 0`` the log-decay a key channel), ``v (b, H, dv)``, ``beta (b,
    H)``; ``layer`` a scalar (traced: one kernel for every layer), ``rows
    (b,)`` int32, distinct but for 0, which says a padded row.

    Each live row's state crosses memory once each way; a padded row,
    wherever it stands in the batch, moves nothing and its ``o`` is zeros;
    state row 0 (the trash row), every state row no batch row names and
    every other layer keep their bits.  All float32 on the vector unit."""
    f32 = lambda x: x.astype(jnp.float32)
    column = lambda x: jnp.swapaxes(f32(x), 1, 2)
    return _kda_slots_call(
        pool, jnp.asarray(layer, jnp.int32).reshape(1),
        rows.astype(jnp.int32), f32(beta).reshape(-1),
        column(jnp.exp(f32(g))), column(k), column(q), f32(v),
        interpret=interpret)


# --------------------------------------------------------------------------
# third resident kernel: one query token a row over the row's LIVE pages of
# the cache's K and V page pools, where they lie


def _paged_kernel(layer_ref, tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, *, pages_a_row, block_pages, groups,
                  scale):
    """One batch row a grid step: the row's tokens ``0 .. position`` go by
    in blocks of ``block_pages`` pages, each page brought by its own DMA
    from ``pool[layer, tables[row, j]]`` into one of two blocks of VMEM (a
    block's pages are on their way while the block before it is computed),
    under a running max, denominator and accumulator in float32.  Only the
    pages that hold a token of the row are asked for; a padded row (its
    first page the trash page) asks for none and gives zeros.

    ``q`` is block-diagonal over the K/V heads (``paged_attention``), so a
    block's scores of every head are one product with the whole key rows
    and its values one product with the whole value rows; each K/V head's
    query heads then keep their own head's columns."""
    b = pl.program_id(0)
    layer, first = layer_ref[0], b * pages_a_row
    block = k_buf.shape[1]
    page = block // block_pages
    tokens = pos_ref[b] + 1
    heads, dv = q_ref.shape[1] // groups, v_buf.shape[2] // groups
    # float32 pools (a block built in float32) multiply at full precision,
    # as the blocks' own products do
    precision = jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32 \
        else None

    def pages_of(blk):
        # the pages of block ``blk`` that hold a token of the row
        return jnp.clip(pl.cdiv(tokens - blk * block, page), 0, block_pages)

    def copies(blk, slot, j):
        at = tables_ref[first + blk * block_pages + j]
        rows = pl.ds(pl.multiple_of(j * page, page), page)
        return (pltpu.make_async_copy(k_hbm.at[layer, at],
                                      k_buf.at[slot, rows], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, at],
                                      v_buf.at[slot, rows], sems.at[1, slot]))

    def each_page(blk, slot, what):
        def one(j, _):
            for c in copies(blk, slot, j):
                what(c)
        jax.lax.fori_loop(0, pages_of(blk), one, None)

    def attend(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < pl.cdiv(tokens, block))
        def _():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())

        # a page of the block that was not asked for holds whatever the
        # buffer held before: its scores are masked, and its values are
        # made zeros, which a probability of zero leaves zeros
        def forget(j, _):
            v_buf[slot, pl.ds(pl.multiple_of(j * page, page), page)] = \
                jnp.zeros((page, v_buf.shape[2]), v_buf.dtype)
        jax.lax.fori_loop(pages_of(blk), block_pages, forget, None)
        v = v_buf[slot]
        s = jax.lax.dot_general(q_ref[0], k_buf[slot], _NT,
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(blk * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1) < tokens, s, _NEG)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        pv = jnp.dot(p.astype(v.dtype), v, precision=precision,
                     preferred_element_type=jnp.float32)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + pv)

    @pl.when(tables_ref[first] != 0)
    def _():
        each_page(0, 0, lambda c: c.start())
        rows = q_ref.shape[1]
        _m, l, acc = jax.lax.fori_loop(
            0, pl.cdiv(tokens, block), attend,
            (jnp.full((rows, 1), _NEG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, v_buf.shape[2]), jnp.float32)))
        out = acc / l
        for g in range(groups):
            o_ref[0, g * heads:(g + 1) * heads] = \
                out[g * heads:(g + 1) * heads, g * dv:(g + 1) * dv]

    @pl.when(tables_ref[first] == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "scale", "block_pages",
                                             "interpret"))
def _paged_call(layer, tables, positions, q, k_pool, v_pool, *, groups, scale,
                block_pages, interpret):
    """``pallas_call`` on the grid (batch rows,) with the layer, the page
    tables (flat: a 2-D array in scalar memory is padded to 128 columns)
    and the positions as prefetched scalars, and the WHOLE pools left where
    they are (``pl.ANY``): the kernel fetches what it needs.  One jitted
    function of its arrays, the layer among them: every layer of every step
    program lowers this once and the chip compiles one body a shape."""
    b, rows, _ = q.shape
    page, kw = k_pool.shape[2:]
    vw = v_pool.shape[3]
    by_row = lambda i, *_: (i, 0, 0)
    return pl.pallas_call(
        functools.partial(_paged_kernel, pages_a_row=tables.shape[1],
                          block_pages=block_pages, groups=groups,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, rows, kw), by_row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, vw // groups), by_row),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * page, kw), k_pool.dtype),
                pltpu.VMEM((2, block_pages * page, vw), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((b, rows, vw // groups), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_attention",
    )(layer, tables.reshape(-1), positions, q, k_pool, v_pool)


def paged_attention(q, k_pool, v_pool, layer, tables, positions, *,
                    block_pages=None, interpret=False):
    """Grouped-query attention of ONE query token a row, ``q (b, g, r, dk)``
    float32 at ``positions (b,)``, over the row's keys and values ``0 ..
    position`` where they lie in the page pools ``k_pool (layers, pages,
    page size, g * dk)`` and ``v_pool (..., g * dv)`` (the token that the
    step just wrote among them): page ``j`` of row ``i`` is ``pool[layer,
    tables[i, j]]``.  ``layer`` a scalar (traced: one kernel for every
    layer).  Returns ``(b, g, r, dv)`` float32.

    Only the pages that hold a token of a live row cross memory, once; a
    page past a row's position, a page no table names, and everything of a
    padded row (``tables[i, 0] == 0``, the trash page; its output is zeros)
    are not read.  The pools are read where they are and keep their bits.

    Products take operands in the pools' dtype and accumulate in float32
    (the probabilities are cast before the value product); the softmax is
    float32 with the scale ``dk ** -0.5``, online over blocks of
    ``block_pages`` pages.

    A head's slice of a key row need not start at a lane tile's edge (4
    heads of 192), so the kernel slices none: ``q`` goes in block-diagonal
    over the K/V heads, ``(g * r, g * dk)`` a row with zeros off a head's
    own columns, and the scores of all heads are one product with the whole
    key rows, the values one product with the whole value rows, of which
    each K/V head's query heads keep their own 128-aligned columns: ``g``
    times the arithmetic of a step whose arithmetic is small."""
    b, g, r, dk = q.shape
    page = k_pool.shape[2]
    if block_pages is None:
        block_pages = max(1, min(tables.shape[1], 512 // page))
    q = (q[:, :, :, None, :] * jnp.eye(g, dtype=q.dtype)[:, None, :, None]
         ).reshape(b, g * r, g * dk).astype(k_pool.dtype)
    out = _paged_call(jnp.asarray(layer, jnp.int32).reshape(1),
                      tables.astype(jnp.int32), positions.astype(jnp.int32),
                      q, k_pool, v_pool, groups=g, scale=dk ** -0.5,
                      block_pages=block_pages, interpret=interpret)
    return out.reshape(b, g, r, -1)


# --------------------------------------------------------------------------
# fourth resident kernel: the same walk over ONE pool whose rows are keys
# and values at once (a latent-attention block's)


def _paged_latent_kernel(layer_ref, tables_ref, pos_ref, q_ref, x_hbm, o_ref,
                         x_buf, sems, *, pages_a_row, block_pages, scale):
    """``_paged_kernel``'s walk (one batch row a grid step, the row's live
    pages in blocks of ``block_pages``, a page a DMA into one of two blocks
    of VMEM, a padded row asks for none and gives zeros) over one pool: a
    page is fetched ONCE and the block in VMEM is both operands, the scores
    ``q . block^T`` of every head and the context ``p . block``."""
    b = pl.program_id(0)
    layer, first = layer_ref[0], b * pages_a_row
    block = x_buf.shape[1]
    page = block // block_pages
    tokens = pos_ref[b] + 1
    precision = jax.lax.Precision.HIGHEST if x_buf.dtype == jnp.float32 \
        else None

    def pages_of(blk):
        # the pages of block ``blk`` that hold a token of the row
        return jnp.clip(pl.cdiv(tokens - blk * block, page), 0, block_pages)

    def rows_of(j):
        return pl.ds(pl.multiple_of(j * page, page), page)

    def each_page(blk, slot, what):
        def one(j, _):
            at = tables_ref[first + blk * block_pages + j]
            what(pltpu.make_async_copy(x_hbm.at[layer, at],
                                       x_buf.at[slot, rows_of(j)],
                                       sems.at[slot]))
        jax.lax.fori_loop(0, pages_of(blk), one, None)

    def attend(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < pl.cdiv(tokens, block))
        def _():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())

        # a page of the block that was not asked for holds whatever the
        # buffer held before: its scores are masked, and as values it is
        # made zeros, which a probability of zero leaves zeros
        def forget(j, _):
            x_buf[slot, rows_of(j)] = jnp.zeros((page, x_buf.shape[2]),
                                                x_buf.dtype)
        jax.lax.fori_loop(pages_of(blk), block_pages, forget, None)
        x = x_buf[slot]
        s = jax.lax.dot_general(q_ref[0], x, _NT, precision=precision,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(blk * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1) < tokens, s, _NEG)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        px = jnp.dot(p.astype(x.dtype), x, precision=precision,
                     preferred_element_type=jnp.float32)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + px)

    @pl.when(tables_ref[first] != 0)
    def _():
        each_page(0, 0, lambda c: c.start())
        heads = q_ref.shape[1]
        _m, l, acc = jax.lax.fori_loop(
            0, pl.cdiv(tokens, block), attend,
            (jnp.full((heads, 1), _NEG, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((heads, x_buf.shape[2]), jnp.float32)))
        o_ref[0] = acc / l

    @pl.when(tables_ref[first] == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_pages",
                                             "interpret"))
def _paged_latent_call(layer, tables, positions, q, pool, *, scale,
                       block_pages, interpret):
    """``_paged_call`` for one pool: the same grid, prefetched scalars and
    whole pool left where it is, one jitted function of its arrays."""
    b, heads, width = q.shape
    page = pool.shape[2]
    by_row = lambda i, *_: (i, 0, 0)
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, pages_a_row=tables.shape[1],
                          block_pages=block_pages, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, heads, width), by_row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, width), by_row),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * page, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, heads, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_latent_attention",
    )(layer, tables.reshape(-1), positions, q, pool)


def paged_latent_attention(q, pool, layer, tables, positions, *, scale,
                           block_pages=None, interpret=False):
    """Attention of ONE query token a row, ``q (b, heads, width)`` float32
    at ``positions (b,)``, over the row's tokens ``0 .. position`` where
    they lie in ONE page pool ``(layers, pages, page size, width)`` whose
    rows are keys AND values, shared by every head (a latent-attention
    block's ``(c_kv | k_r | 0...)``, the query folded into that space):
    ``softmax(scale * q . rows^T) . rows``, ``(b, heads, width)`` float32,
    of which the caller keeps the columns that are values.  ``scale`` is the
    caller's (YaRN's, not a head width's); ``layer``, ``tables`` and the
    padded row as in :func:`paged_attention`.

    Only the pages that hold a token of a live row cross memory, ONCE for
    both products; the pool is read where it is and keeps its bits.
    Products take operands in the pool's dtype and accumulate in float32
    (the probabilities are cast before the context product); the softmax is
    float32, online over blocks of ``block_pages`` pages (32 pages of 16
    tokens of 640 bfloat16 values are 655 KB of VMEM a block, of two)."""
    page = pool.shape[2]
    if block_pages is None:
        block_pages = max(1, min(tables.shape[1], 512 // page))
    return _paged_latent_call(
        jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
        positions.astype(jnp.int32), q.astype(pool.dtype), pool,
        scale=float(scale), block_pages=block_pages, interpret=interpret)


# --------------------------------------------------------------------------
# kernel on the chip, definition on the CPU, and a count of which was built


_lowered_as_p = jax.extend.core.Primitive("lowered_as")
_lowered_as_p.def_impl(lambda x, **_: x)
_lowered_as_p.def_abstract_eval(lambda x, **_: x)


def _lowered_as_lowering(ctx, x, *, counter, labels):
    from ..telemetry import bus as _tel
    _tel.count(counter, **dict(labels))
    return [x]


# not cacheable: every instance counts, not the first of its shape
jax.interpreters.mlir.register_lowering(_lowered_as_p, _lowered_as_lowering,
                                        cacheable=False)


def by_platform(counter, *args, kernel, plain, **labels):
    """``kernel(*args)`` where the call is lowered for the chip,
    ``plain(*args)`` where it is lowered for the CPU (the tests; a program
    built on the host), as ``flash_attention`` chooses its interpreter:
    nothing a caller sets decides.  Both are traced and ONE is lowered; the
    telemetry counter ``counter`` counts it then, with ``kind="kernel"`` or
    ``"plain"`` and ``labels`` (an identity on the first array of ``args``,
    whose lowering rule does the counting: a trace cannot know)."""
    def counted(fn, kind):
        items = tuple(sorted(dict(labels, kind=kind).items()))

        def branch(*args):
            (first, *rest), tree = jax.tree.flatten(args)
            first = _lowered_as_p.bind(first, counter=counter, labels=items)
            return fn(*jax.tree.unflatten(tree, [first] + rest))
        return branch

    return jax.lax.platform_dependent(*args, cpu=counted(plain, "plain"),
                                      default=counted(kernel, "kernel"))


# --------------------------------------------------------------------------
# fifth resident pair: the hyper-connections of one sublayer of a residual
# path of ``n`` streams (``ops.hyper_connection`` is their definition),
# two kernels around the sublayer's ``f`` on a grid of token blocks, so that
# one pair of bodies serves a decode step's rows (one block) and a prefill's
# tokens (128 a block).  ``hc_pre``: a block of streams read once; its mean
# square; the ``n (n + 2)`` coefficients ``x phi`` to float32 accuracy
# (multiply-accumulates on the vector unit for a step's rows, the MXU at the
# highest precision for a prefill's block); sigmoids, the clipped
# exponential and EVERY Sinkhorn round on a token a lane and an entry a
# sublane, values that never leave the core; the read ``Hpre X`` from the
# block still held.  ``hc_post``: ``Hres X + Hpost^T y``, the block read once
# more and written where it lay.  Two launches and three crossings of the
# streams a sublayer, where XLA made thirty fusions, a loop of two launches a
# round, and five crossings.  ``serving.decode.latent_moe.LatentMoELM.
# _sublayer`` is their one caller, through ``by_platform``.
#
# (Down here, past ``by_platform``, and not in the module's docstring or
# among its imports: a kernel's lowered body carries the line numbers of the
# frames that built it, and the programs of every block above must keep
# lowering to the text they had.)

from .hyper_connection import COEF_LANES  # noqa: E402

__all__ += ["hc_pre", "hc_post"]

_HC_BLOCK = 128     # tokens a block; fewer (a decode step's rows) are one


def _hc_rounds(res, n, iters, eps):
    """``iters`` Sinkhorn-Knopp rounds on ``res (n n, L)``, entry ``i, j`` of
    a token's matrix at sublane ``i n + j`` and a token a lane, so that a
    round is a dozen operations on whole registers whatever ``L``: the
    columns first (each entry over its column's sum + eps), then the rows,
    true divisions (``ops.hyper_connection.sinkhorn`` is the definition)."""
    def one_round(_, rows):
        col = sum(rows[1:], rows[0]) + eps
        rows = [r / col for r in rows]
        return tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps)
                     for r in rows)

    return jax.lax.fori_loop(
        0, iters, one_round, tuple(res[i * n:(i + 1) * n] for i in range(n)))


def _hc_row_groups(t, body):
    """``body(first row, rows)`` for the ``t`` rows of a block eight at a
    time (a register's sublanes), then the tail."""
    if t >= 8:
        def full(g, _):
            body(pl.multiple_of(g * 8, 8), 8)
        jax.lax.fori_loop(0, t // 8, full, None)
    if t % 8:
        body(t // 8 * 8, t % 8)


def _hc_tile(c):
    """Lanes ``c .. c + 128`` of a row (``c`` a multiple of 128)."""
    return pl.ds(pl.multiple_of(c, 128), 128)


def _hc_products(live_ref, x_ref, phit_ref, zt_ref, ss_ref):
    """``phi^T x`` into ``zt_ref (K, L)`` and ``sum x^2`` into ``ss_ref (1,
    L)`` for the tokens ``x_ref (T, n C)``, to float32 accuracy, a token a
    lane.  A whole block of a prefill's tokens: one product on the MXU at
    the highest precision, where the vector unit would take 24 passes over
    the block.  Fewer (a decode step's rows): multiply-accumulates on the
    vector unit, eight tokens at a time against the whole of ``phi^T``, 128
    lanes a term, where the MXU would load 112 tiles six times for a few
    rows; eight rows of which ``live_ref`` names none (the padding behind a
    step's few rows) are passed over and read as a zero stream's."""
    t, width = x_ref.shape
    k = phit_ref.shape[0]
    if t == _HC_BLOCK:
        x = x_ref[...]
        zt_ref[...] = jax.lax.dot_general(
            phit_ref[...], x, _NT, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        ss_ref[...] = _col_to_row(jnp.sum(x * x, axis=1, keepdims=True))
        return
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _HC_BLOCK), 1)
    zeros = lambda r: jnp.zeros((r, 128), jnp.float32)
    zt_ref[...] = jnp.zeros(zt_ref.shape, jnp.float32)
    ss_ref[...] = jnp.ones(ss_ref.shape, jnp.float32)

    @functools.partial(_hc_row_groups, t)
    def group(base, rows):
        def term(c, acc):
            at = _hc_tile(c * 128)
            xc, ph = x_ref[pl.ds(base, rows), at], phit_ref[:, at]
            return tuple(acc[r] + ph * xc[r:r + 1] for r in range(rows)) \
                + (acc[rows] + xc * xc,)

        @pl.when(sum(live_ref[base + r] for r in range(rows)) > 0)
        def _():
            acc = jax.lax.fori_loop(
                0, width // 128, term,
                tuple(zeros(k) for _ in range(rows)) + (zeros(rows),))
            for r in range(rows):
                hit = lane == base + r
                zt_ref[...] = jnp.where(
                    hit, jnp.sum(acc[r], axis=1, keepdims=True), zt_ref[...])
                ss_ref[...] = jnp.where(
                    hit, jnp.sum(acc[rows][r:r + 1], axis=1, keepdims=True),
                    ss_ref[...])


def _hc_pre_kernel(a_ref, b_ref, live_ref, x_ref, phit_ref, u_ref, coef_ref,
                   zt_ref, ss_ref, *, n, iters, eps, clamp):
    """One block of tokens before ``f``: the coefficients of
    ``ops.hyper_connection.hc_coefficients`` with every Sinkhorn round on
    values that never leave the core, and the read ``u = Hpre X`` from the
    block still held.  ``coef_ref (T, 128)``: a token's ``Hpre | Hpost |
    Hres`` (row-major) in its first ``n (n + 2)`` lanes."""
    t, width = x_ref.shape
    c, k = width // n, n * (n + 2)
    _hc_products(live_ref, x_ref, phit_ref, zt_ref, ss_ref)
    inv = jax.lax.rsqrt(ss_ref[...] / width + eps)
    row = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    scale = jnp.where(row < n, a_ref[0],
                      jnp.where(row < 2 * n, a_ref[1], a_ref[2]))
    bias = jnp.zeros((k, 1), jnp.float32)
    for j in range(k):
        bias = jnp.where(row == j, b_ref[j], bias)
    z = zt_ref[...] * inv * scale + bias
    gate = jax.nn.sigmoid(z[:2 * n])
    gate = jnp.where(row[:2 * n] < n, gate, 2.0 * gate)
    res = _hc_rounds(jnp.exp(jnp.clip(z[2 * n:], clamp[0], clamp[1])), n,
                     iters, eps)
    # a coefficient a sublane -> a token a sublane: a whole-tile transpose
    coef_t = jnp.concatenate(
        (gate,) + tuple(res)
        + (jnp.zeros((COEF_LANES - k, _HC_BLOCK), jnp.float32),), axis=0)
    coef_ref[...] = coef_t.T[:t]

    @functools.partial(_hc_row_groups, t)
    def read(base, rows):
        at = pl.ds(base, rows)
        own = coef_ref[at, :]
        h = [jnp.broadcast_to(own[:, j:j + 1], (rows, 128)) for j in range(n)]

        def tile(i, _):
            u_ref[at, _hc_tile(i * 128)] = sum(
                h[j] * x_ref[at, _hc_tile(j * c + i * 128)]
                for j in range(n))

        jax.lax.fori_loop(0, c // 128, tile, None)


def _hc_post_kernel(coef_ref, y_ref, x_ref, o_ref, *, n):
    """One block of tokens after ``f``: ``X'[i] = sum_j Hres[i, j] X[j] +
    Hpost[i] y`` (``ops.hyper_connection.hc_write``), the block read once
    and written where it lay; eight tokens' 20 coefficients stay in
    registers while their streams go by, 128 lanes a turn."""
    t, c = y_ref.shape

    @functools.partial(_hc_row_groups, t)
    def write(base, rows):
        at = pl.ds(base, rows)
        own = coef_ref[at, :]
        h = [jnp.broadcast_to(own[:, j:j + 1], (rows, 128))
             for j in range(n * (n + 2))]

        def tile(l, _):
            y = y_ref[at, _hc_tile(l * 128)].astype(jnp.float32)
            x = [x_ref[at, _hc_tile(j * c + l * 128)] for j in range(n)]
            for i in range(n):
                o_ref[at, _hc_tile(i * c + l * 128)] = sum(
                    (h[2 * n + i * n + j] * x[j] for j in range(n)),
                    h[n + i] * y)

        jax.lax.fori_loop(0, c // 128, tile, None)


def _hc_call(kernel, name, t, width, **kw):
    """``pallas_call`` on the grid (token blocks,): ``_HC_BLOCK`` tokens a
    block (7.3 MB of float32 streams at 4 x 3,584; in, out and their second
    buffers are four), the ragged last block's rows past the end read as
    they come and never written; a step's rows are one block."""
    block = min(t, _HC_BLOCK)
    spec = lambda lanes: pl.BlockSpec((block, lanes), lambda i: (i, 0))
    return spec, functools.partial(
        pl.pallas_call, kernel, grid=(pl.cdiv(t, block),),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=5 * block * width * 4 + (16 << 20)),
        name=name, **kw)


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp",
                                             "interpret"))
def _hc_pre_call(x, phi_t, a, b, live, *, n, iters, eps, clamp, interpret):
    t, width = x.shape
    spec, call = _hc_call(
        functools.partial(_hc_pre_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp), "hc_pre", t, width,
        interpret=interpret)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return call(
        in_specs=[smem, smem, smem, spec(width),
                  pl.BlockSpec(phi_t.shape, lambda i: (0, 0))],
        out_specs=(spec(width // n), spec(COEF_LANES)),
        out_shape=(jax.ShapeDtypeStruct((t, width // n), jnp.float32),
                   jax.ShapeDtypeStruct((t, COEF_LANES), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((phi_t.shape[0], _HC_BLOCK), jnp.float32),
                        pltpu.VMEM((1, _HC_BLOCK), jnp.float32)],
    )(a, b, live, x, phi_t)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _hc_post_call(x, coef, y, *, n, interpret):
    t, width = x.shape
    spec, call = _hc_call(functools.partial(_hc_post_kernel, n=n), "hc_post",
                          t, width, interpret=interpret)
    return call(
        in_specs=[spec(COEF_LANES), spec(width // n), spec(width)],
        out_specs=spec(width),
        out_shape=jax.ShapeDtypeStruct((t, width), jnp.float32),
        input_output_aliases={2: 0},
    )(coef, y, x)


def hc_pre(X, phi_t, a, b, live=None, *, iters, eps, clamp, interpret=False):
    """The first half of one sublayer's hyper-connections for streams ``X
    (..., n, C)`` float32 (``ops.hyper_connection.hc_coefficients`` and
    ``hc_read``, which are its definition): ``(u (..., C), coef (T, 128))``,
    the sublayer's input ``Hpre X`` and each of the ``T`` tokens'
    coefficients ``Hpre | Hpost | Hres`` in the first ``n (n + 2)`` lanes of
    a lane tile (``ops.hyper_connection.coef_tile``), as :func:`hc_post`
    reads them.  ``phi_t (n (n + 2), n C)`` is ``phi`` with its long axis
    minor (as registered, its 24 columns tile to 128 lanes).  The streams
    cross memory once, ``phi`` once a call, and the rounds run on registers.
    ``C`` is whole lane tiles.  ``live (...)``, where given, marks the
    tokens that count: among fewer than 128 (a step's rows) eight in a row
    that are all padding get the coefficients of a zero stream, and their
    share of the product is not computed."""
    lead, (n, c) = X.shape[:-2], X.shape[-2:]
    x = X.astype(jnp.float32).reshape((-1, n * c))
    live = jnp.ones(x.shape[:1], jnp.int32) if live is None \
        else live.reshape(-1).astype(jnp.int32)
    u, coef = _hc_pre_call(
        x, phi_t, a.astype(jnp.float32), b.astype(jnp.float32), live, n=n,
        iters=int(iters), eps=float(eps),
        clamp=(float(clamp[0]), float(clamp[1])), interpret=interpret)
    return u.reshape(lead + (c,)), coef


def hc_post(X, coef, y, *, interpret=False):
    """The second half: the streams a sublayer leaves, ``Hres X + Hpost^T
    y`` (``ops.hyper_connection.hc_write``), from :func:`hc_pre`'s ``coef``
    and the sublayer's output ``y (..., C)``; ``X`` is read once more and
    written where it lay."""
    n, c = X.shape[-2:]
    return _hc_post_call(X.astype(jnp.float32).reshape((-1, n * c)), coef,
                         y.reshape((-1, c)), n=n,
                         interpret=interpret).reshape(X.shape)


# --------------------------------------------------------------------------
# sixth resident kernel: one query token a row over TWO operand pairs under
# ONE softmax, each read where it lies and only where it is live: the OPEN
# window's exact keys and values in the slot's rings (entries ``0 .. position
# mod window``; "same window as the query", not "the last W") and the chunk
# summaries of the CLOSED windows in the row's pages (rows ``0 .. position //
# window * window / row_tokens - 1``).  ``paged_attention``'s walk with a
# second operand: one batch row a grid step, blocks of ``block`` columns
# double-buffered through two blocks of VMEM a pool, a ring block ONE
# contiguous DMA a pool and a summary block a DMA a page, the second buffer
# filling across the seam between the two; a running max, denominator and
# accumulator in float32 carried through all of them, ``o = acc / l`` once.
# The scores are joined, never the keys.  ``serving.decode.kv_format.
# PageFormat.attend_window`` is its one caller, through ``by_platform``.
#
# Rows and ring entries are stored by head, ``(heads, head_dim)``, and one
# query a K/V head is no shape for the MXU as it lies.  A block in VMEM is
# read as ``(block * heads, head_dim)`` (a free view: the heads are whole
# sublane tiles) and every head's query meets every (column, head) row in
# ONE product, ``q (heads, head_dim) . rows^T -> (heads, block * heads)``, of
# which a head keeps the columns that are its own (``column mod heads ==
# head``; the others are masked out of the softmax like dead columns) and
# the context is ``p . rows -> (heads, head_dim)``: ``heads`` times the
# arithmetic of a step whose arithmetic is small, as ``paged_attention``'s
# block-diagonal ``q`` is, on lane-dense tiles with no reduction across
# lanes and nothing laid out anew.
#
# (Down here for the reason the fifth pair is: the line numbers above stay.)

__all__ += ["eva_attention"]


def _eva_kernel(layer_ref, tables_ref, rows_ref, pos_ref, q_ref, sk_hbm,
                sv_hbm, rk_hbm, rv_hbm, o_ref, k_buf, v_buf, sems, *,
                pages_a_row, block_pages, per_window, scale):
    """One batch row a grid step.  Blocks ``0 .. ring_blocks - 1`` are the
    ring's (``ring[layer, state row, i block : (i + 1) block]``, fetched
    whole: the entries of the last one past ``position mod window`` hold
    what the closed window or the slot's last owner left and are masked),
    the blocks behind them the summaries' (``block_pages`` pages each, page
    ``j`` from ``pool[layer, tables[row, j]]``, only those that hold a row
    of a closed window).  A row in its first window has no summary block; a
    padded row (its first page the trash page) asks for nothing and gives
    zeros."""
    b = pl.program_id(0)
    layer, first, state_row = layer_ref[0], b * pages_a_row, rows_ref[b]
    _slots, block, heads, width = k_buf.shape
    page, window = block // block_pages, rk_hbm.shape[2]
    entries = jax.lax.rem(pos_ref[b], window) + 1
    summaries = jax.lax.div(pos_ref[b], window) * per_window
    ring_blocks = pl.cdiv(entries, block)
    blocks = ring_blocks + pl.cdiv(summaries, block)
    pairs = ((rk_hbm, sk_hbm, k_buf), (rv_hbm, sv_hbm, v_buf))
    precision = jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32 \
        else None

    def live_of(i):
        # the columns of block ``i`` that the query may read
        return jnp.minimum(block, jnp.where(
            i < ring_blocks, entries - i * block,
            summaries - (i - ring_blocks) * block))

    def each_copy(i, slot, what):
        @pl.when(i < ring_blocks)
        def _():
            at = pl.ds(pl.multiple_of(i * block, block), block)
            for n, (ring, _pool, buf) in enumerate(pairs):
                what(pltpu.make_async_copy(ring.at[layer, state_row, at],
                                           buf.at[slot], sems.at[n, slot]))

        @pl.when(i >= ring_blocks)
        def _():
            def one(j, _):
                at = tables_ref[first + (i - ring_blocks) * block_pages + j]
                rows = pl.ds(pl.multiple_of(j * page, page), page)
                for n, (_ring, pool, buf) in enumerate(pairs):
                    what(pltpu.make_async_copy(pool.at[layer, at],
                                               buf.at[slot, rows],
                                               sems.at[n, slot]))
            jax.lax.fori_loop(0, pl.cdiv(live_of(i), page), one, None)

    # column ``j`` of a block's scores is (column j // heads, head j mod
    # heads): a head's own are those of its number
    column = jax.lax.broadcasted_iota(jnp.int32, (heads, block * heads), 1)
    own = jax.lax.rem(column, heads) == jax.lax.broadcasted_iota(
        jnp.int32, (heads, block * heads), 0)

    def attend(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < blocks)
        def _():
            each_copy(i + 1, 1 - slot, lambda c: c.start())

        each_copy(i, slot, lambda c: c.wait())
        live = live_of(i)

        # a column past the live ones holds what the ring held there, or
        # what the buffer held before: its scores are masked, and its values
        # are made zeros, which a probability of zero leaves zeros
        def forget(e, _):
            v_buf[slot, e] = jnp.zeros((heads, width), v_buf.dtype)
        jax.lax.fori_loop(live, block, forget, None)
        k = k_buf[slot].reshape(block * heads, width)
        v = v_buf[slot].reshape(block * heads, width)
        s = jax.lax.dot_general(q_ref[0], k, _NT, precision=precision,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(jnp.logical_and(own, column < live * heads), s, _NEG)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        pv = jnp.dot(p.astype(v.dtype), v, precision=precision,
                     preferred_element_type=jnp.float32)
        return (new_m, l * corr + jnp.sum(p, axis=-1, keepdims=True),
                acc * corr + pv)

    @pl.when(tables_ref[first] != 0)
    def _():
        each_copy(0, 0, lambda c: c.start())
        _m, l, acc = jax.lax.fori_loop(
            0, blocks, attend,
            (jnp.full((heads, 1), _NEG, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((heads, width), jnp.float32)))
        o_ref[0] = acc / l

    @pl.when(tables_ref[first] == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tokens", "block_pages",
                                             "interpret"))
def _eva_call(layer, tables, rows, positions, q, sk_pool, sv_pool, ring_k,
              ring_v, *, row_tokens, block_pages, interpret):
    """``pallas_call`` on the grid (batch rows,) with the layer, the flat
    page tables, the rows' state rows and the positions as prefetched
    scalars and the four WHOLE pools left where they are (``pl.ANY``): the
    kernel fetches what it needs.  One jitted function of its arrays, the
    layer among them: every layer of every step program lowers this once and
    the chip compiles one body a shape.  Two blocks a pool of VMEM: 128
    columns of 32 heads of 128 in bfloat16 are 1 MB, so 4 MB, and the
    scores, probabilities and masks of a block 0.5 MB each beside them."""
    b, heads, width = q.shape
    page, window = sk_pool.shape[2], ring_k.shape[2]
    block = block_pages * page
    buffers = lambda pool: pltpu.VMEM((2, block, heads, width), pool.dtype)
    by_row = pl.BlockSpec((1, heads, width), lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_eva_kernel, pages_a_row=tables.shape[1],
                          block_pages=block_pages,
                          per_window=window // row_tokens,
                          scale=width ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b,),
            in_specs=[by_row] + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=by_row,
            scratch_shapes=[buffers(sk_pool), buffers(sv_pool),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * block * heads * width
            * sk_pool.dtype.itemsize + 12 * block * heads * heads * 4
            + (8 << 20)),
        interpret=interpret, name="eva_attention",
    )(layer, tables.reshape(-1), rows, positions, q, sk_pool, sv_pool,
      ring_k, ring_v)


def eva_attention(q, sk_pool, sv_pool, ring_k, ring_v, layer, tables, rows,
                  positions, *, row_tokens, block_pages=None,
                  interpret=False):
    """Attention of ONE query token a row, ``q (b, heads, head_dim)``
    float32 at ``positions (b,)``, under ONE softmax (scale ``head_dim **
    -0.5``) over two kinds of column, each where it lies:

    - the exact keys and values of the query's OWN window: entries ``0 ..
      position mod window`` of the row's rings, ``ring_k``, ``ring_v
      (layers, state rows, window, heads, head_dim)`` at ``[layer,
      rows[i]]`` (the entry this step wrote among them);
    - the summaries of the windows BEFORE it: rows ``0 .. position // window
      * (window // row_tokens) - 1`` of the row's pages of ``sk_pool``,
      ``sv_pool (layers, pages, page size, heads, head_dim)``, page ``j`` of
      row ``i`` at ``pool[layer, tables[i, j]]``, a row standing for
      ``row_tokens`` positions.  The row this step wrote belongs to the open
      window and is never read here.

    ``layer`` a scalar (traced: one kernel for every layer).  Returns ``(b,
    heads, head_dim)`` float32.

    Only blocks of the ring that hold a live entry and pages that hold a
    closed window's row cross memory, once; ring entries past ``position
    mod window`` inside the last block are fetched with it and masked (as
    values, made zeros); ring blocks and pages past those, pages no table
    names, other slots' rings and everything of a padded row (``tables[i,
    0] == 0``, the trash page; its output is zeros) are not read.  The pools
    are read where they are and keep their bits.

    Products take operands in the pools' dtype (``q`` and the probabilities
    are cast before them) and accumulate in float32; the softmax is float32,
    online over blocks of ``block_pages`` pages' rows, which must divide the
    window: 128 columns where nothing is said (on the chip, EvaByte's
    widths, three live rows of eight: 97.0 us a layer against 104.7 at 256
    columns and 119.3 at 512, a shorter first block to wait for and less of
    the ring's last block read dead; my chip run, PR 44).  The heads must
    fill whole sublane tiles on the chip (16 in bfloat16)."""
    page, window = sk_pool.shape[2], ring_k.shape[2]
    if block_pages is None:
        block_pages = max(1, math.gcd(window, 128) // page)
    if window % (block_pages * page):
        raise ValueError(
            f"a block of {block_pages} pages of {page} rows does not divide "
            f"the window of {window}: a ring block is one slice of the ring")
    i32 = lambda x: x.astype(jnp.int32)
    return _eva_call(jnp.asarray(layer, jnp.int32).reshape(1), i32(tables),
                     i32(rows), i32(positions), q.astype(sk_pool.dtype),
                     sk_pool, sv_pool, ring_k, ring_v,
                     row_tokens=int(row_tokens), block_pages=block_pages,
                     interpret=interpret)


# --------------------------------------------------------------------------
# seventh resident kernel: the causal attention of a latent-attention block's
# whole padded prompt in the EXPANDED form (``serving.decode.latent_moe.
# LatentMoELM.attend_expanded`` is its definition), on the grid (prompt, pair
# of heads, query block, key block) with the score tile in VMEM only: no
# ``(heads, S, S)`` array is written, where XLA's chain (two einsums added, a
# ``where``, a softmax, a cast) passes one through device memory five times a
# layer.  The operands lie as the projections leave them, heads side by side
# in a row: the unrotated queries ``(S, H nope)``, the rotated ones ``(S, H
# rope)``, the per-head keys and values out of ``W_kvb`` as ONE array ``(S, H
# (nope + v))`` (a head's ``k_nope`` and ``v`` side by side), and the rotated
# key ``(S, rope)`` that every head shares, so a tile's scores are two
# products into one float32 tile, ``q_nope . k_nope^T + q_rope . k_r^T`` (one
# product over their 192-wide concatenation takes the same time to the
# microsecond: my chip run, PR 45).  Key blocks wholly past a query block's
# diagonal are neither fetched (their index is the last live block's, and a
# block that is named again is not moved) nor computed; in the block the
# diagonal crosses only the keys up to the query block's last are read, and
# only there is the mask built; the running max, denominator and accumulator
# are float32 scratch and the division is made once, at the query block's
# last live key block.  Forward only, no bias, no dropout, no log-sum-exp: a
# prompt's padding needs none (keys past every valid query, queries nobody
# reads).  ``LatentMoELM.prefill_math`` is its one caller, through
# ``by_platform``.
#
# (Down here for the reason the fifth pair is: the line numbers above stay.)

__all__ += ["mla_prefill_attention"]


def _mla_last_live(qi, bq, bk):
    """The last key block a query of block ``qi`` may read."""
    return (qi * bq + bq - 1) // bk


def _mla_prefill_kernel(qn_ref, qr_ref, kv_ref, kr_ref, o_ref, m_sc, l_sc,
                        acc_sc, *, scale, heads, nope, rope, width):
    """One (prompt, group of ``heads`` heads, q block, k block) step.

    Blocks: qn (1, BQ, heads nope); qr (1, BQ, heads rope); kv (1, BK, heads
    (nope + width)), a head's keys then its values; kr (1, BK, rope); o (1,
    BQ, heads width).  Scratch: m, l (heads, BQ, 1) and acc (BQ, heads
    width), float32."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = qn_ref.shape[1], kv_ref.shape[1]
    last = _mla_last_live(qi, bq, bk)
    precision = jax.lax.Precision.HIGHEST if kv_ref.dtype == jnp.float32 \
        else None
    dot = functools.partial(jax.lax.dot_general, precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(ki == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def tile(keys, crossed):
        """The block's first ``keys`` keys under the queries; ``crossed``:
        some of them lie past some query, and the mask is built."""
        kr = kr_ref[0, :keys]
        if crossed:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, keys),
                                                       1)
            seen = q_pos >= k_pos
        for a in range(heads):
            at = a * (nope + width)
            s = (dot(qn_ref[0, :, a * nope:(a + 1) * nope],
                     kv_ref[0, :keys, at:at + nope], _NT)
                 + dot(qr_ref[0, :, a * rope:(a + 1) * rope], kr, _NT)) \
                * scale
            if crossed:
                s = jnp.where(seen, s, _NEG)
            m = m_sc[a]
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - new_m)
            corr = jnp.exp(m - new_m)
            l_sc[a] = l_sc[a] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_sc[a] = new_m
            v = kv_ref[0, :keys, at + nope:at + nope + width]
            lanes = slice(a * width, (a + 1) * width)
            acc_sc[:, lanes] = acc_sc[:, lanes] * corr + dot(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())))

    # every key of the block at or before every query of the block: no mask
    whole = ki * bk + bk - 1 <= qi * bq
    pl.when(whole)(functools.partial(tile, bk, False))
    # a block the diagonal crosses.  Where it holds more keys than the query
    # block queries, the queries begin ``j bq`` keys into it and may read its
    # first ``(j + 1) bq``: a case a ``j``, each of a static width
    crossed = jnp.logical_and(jnp.logical_not(whole), ki <= last)
    if bk <= bq:
        pl.when(crossed)(functools.partial(tile, bk, True))
    else:
        for j in range(bk // bq):
            pl.when(jnp.logical_and(crossed, qi * bq - ki * bk == j * bq))(
                functools.partial(tile, (j + 1) * bq, True))

    @pl.when(ki == last)
    def _():
        for a in range(heads):
            lanes = slice(a * width, (a + 1) * width)
            o_ref[0, :, lanes] = (acc_sc[:, lanes] / l_sc[a]).astype(
                o_ref.dtype)


def _mla_blocks(s):
    """(BQ, BK) for a prompt of ``s`` positions (a multiple of 128): 256
    queries a block where that divides ``s`` and as many keys as divide it,
    up to 2,048: a prompt of up to 2,048 positions is ONE key block and a
    query block one step.  On the chip, 32 heads (microseconds a layer at 512
    / 1,024 / 1,536 / 2,048 positions, my chip runs, PR 45): 113 / 223 / 377
    / 585 so, 113 / 261 / 473 / 750 with 512 keys a block, 139 / 373 / 731 /
    1,214 with 256: what a step pays whatever its keys (the accumulator read
    and written, the statistics a lane a row) is paid once."""
    bq = 256 if s % 256 == 0 else 128
    return bq, max(bk for bk in range(bq, 2048 + 1, bq) if s % bk == 0)


@functools.partial(jax.jit, static_argnames=("scale", "heads", "block_q",
                                             "block_k", "interpret"))
def _mla_prefill_call(q_nope, q_rope, kv, kr, *, scale, heads, block_q,
                      block_k, interpret):
    """``pallas_call`` on the grid (prompts, pairs of heads, q blocks, k
    blocks).  One jitted function of its arrays: every layer of a prefill
    program lowers this once and the chip compiles one body a bucket.  At
    256 x 2,048 a pair's keys and values are 2 MB a buffer and a score tile
    2 MB of float32, the probabilities as much again."""
    b, s, _ = q_nope.shape
    rope = kr.shape[2]
    nope = q_nope.shape[2] // heads
    width = kv.shape[2] // heads - nope
    group = 2               # two rotated queries of 64 fill a lane tile
    bq, bk = block_q, block_k
    live = lambda j, kk: jnp.minimum(kk, _mla_last_live(j, bq, bk))
    by_q = lambda lanes: pl.BlockSpec((1, bq, group * lanes),
                                      lambda i, h, j, kk: (i, j, h))
    return pl.pallas_call(
        functools.partial(_mla_prefill_kernel, scale=scale, heads=group,
                          nope=nope, rope=rope, width=width),
        grid=(b, heads // group, s // bq, s // bk),
        in_specs=[by_q(nope), by_q(rope),
                  pl.BlockSpec((1, bk, group * (nope + width)),
                               lambda i, h, j, kk: (i, live(j, kk), h)),
                  pl.BlockSpec((1, bk, rope),
                               lambda i, h, j, kk: (i, live(j, kk), 0))],
        out_specs=by_q(width),
        out_shape=jax.ShapeDtypeStruct((b, s, heads * width), kv.dtype),
        scratch_shapes=[pltpu.VMEM((group, bq, 1), jnp.float32),
                        pltpu.VMEM((group, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, group * width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name="mla_prefill_attention",
    )(q_nope, q_rope, kv, kr)


def mla_prefill_attention(q_nope, q_rope, kv, kr, *, scale, block_q=None,
                          block_k=None, interpret=False):
    """Causal attention of whole padded prompts in a latent-attention
    block's expanded form: position ``t`` of a prompt attends to positions
    ``0 .. t`` of it with scores ``scale (q_nope . k_nope + q_rope . k_r)``.

    ``q_nope (B, S, H, nope)`` and ``q_rope (B, S, H, rope)`` are the heads'
    queries (the second rotated), ``kv (B, S, H, nope + v)`` a head's
    unrotated keys and its values side by side as ``W_kvb`` gives them, and
    ``kr (B, S, rope)`` the rotated key all heads share.  Returns ``(B, S, H
    v)`` in ``kv``'s dtype.  Nothing of ``(H, S, S)`` is written: a score
    tile lives in VMEM, key blocks past a query block's diagonal are neither
    fetched nor computed.  Operands enter the products in ``kv``'s dtype (the
    queries and the probabilities are cast to it), accumulation and the
    softmax's statistics are float32.  ``S`` is a multiple of the blocks
    (``_mla_blocks`` where none is named; one of them divides the other),
    ``nope`` and ``v`` are whole lane tiles and a pair of heads' ``rope``
    one, and the heads are even in number."""
    b, s, h, _ = q_nope.shape
    dt = kv.dtype
    bq, bk = _mla_blocks(s)
    bq, bk = block_q or bq, block_k or bk
    if s % bq or s % bk or max(bq, bk) % min(bq, bk):
        raise ValueError(f"mla_prefill_attention: blocks of {bq} queries "
                         f"and {bk} keys for {s} positions")
    flat = lambda x: x.astype(dt).reshape(b, s, -1)
    return _mla_prefill_call(
        flat(q_nope), flat(q_rope), flat(kv), kr.astype(dt),
        scale=float(scale), heads=h, block_q=bq, block_k=bk,
        interpret=interpret)
