"""Pallas TPU kernels for the hot ops.

The reference's answer to "the framework op isn't fast enough" was
hand-written CUDA (``src/operator/*.cu``) or NVRTC runtime compilation
(``mx.rtc``, src/common/rtc.cc); the TPU-native answer is Pallas.  First
resident kernel: **flash attention** — blockwise online-softmax attention
that never materializes the T×T score matrix, streaming K/V blocks from
VMEM while the running max/denominator stay in registers (the memory story
behind the sequence-parallel design, SURVEY.md §5.7).

The public entry ``flash_attention`` is differentiable: forward runs the
kernel, backward recomputes with the plain XLA formulation (standard
flash-attention recompute trade — backward FLOPs for O(T²) memory).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

_NEG = -1e30

# Mosaic's default scoped-VMEM budget for one kernel on a v5e core; the
# chip's compiler refuses a kernel whose blocks need more
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, scale,
               seq_len, kv_len):
    """One (batch*head, q-block) program: stream K/V blocks, online softmax.

    Block shapes: q (1, BQ, D), k/v (1, T, D), o (1, BQ, D).  ``seq_len``
    is the padded T, ``kv_len`` the real one: keys at or past it are the
    zero padding and are masked out (causal masking already hides them
    from every real query row).
    """
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)

    m0 = jnp.full((bq, 1), _NEG, dtype=jnp.float32)
    l0 = jnp.zeros((bq, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((bq, d), dtype=jnp.float32)
    num_k = seq_len // block_k

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)
        if causal or kv_len < seq_len:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            keep = q_pos >= k_pos if causal else k_pos < kv_len
            s = jnp.where(keep, s, _NEG)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        new_acc = acc * corr + jnp.dot(p, v,
                                       preferred_element_type=jnp.float32)
        return new_m, new_l, new_acc

    if causal:
        # skip fully-masked K blocks: block j is live iff j*BK <= last q pos
        last_q = qi * bq + bq - 1
        num_live = jnp.minimum((last_q // block_k) + 1, num_k)
    else:
        num_live = num_k
    m, l, acc = jax.lax.fori_loop(0, num_live, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _fa_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, t, d = q.shape
    orig_t, orig_d = t, d
    # pad D to the 128-lane tile and T to the block size; the kernel masks
    # the padded key positions by length
    pad_d = (-d) % 128
    block = max(block_q, block_k)
    pad_t = (-t) % block
    if pad_d or pad_t:
        cfg = [(0, 0), (0, 0), (0, pad_t), (0, pad_d)]
        q = jnp.pad(q, cfg)
        k = jnp.pad(k, cfg)
        v = jnp.pad(v, cfg)
        t, d = t + pad_t, d + pad_d
    # K and V ride as whole-sequence blocks, double-buffered like the q/o
    # blocks, beside the kernel's f32 scores, probabilities and accumulator.
    # Past the budget the chip's compiler refuses the kernel ("scoped vmem
    # limit"), so say so here with the numbers.  The estimate matches the
    # v5e compiler's verdict at 128x128 blocks: bf16 passes to 16,000
    # tokens, f32 to 7,936, at head_dim <= 128.
    itemsize = jnp.dtype(q.dtype).itemsize
    fixed = 4 * block_q * d * itemsize + \
        4 * (2 * block_q * block_k + block_q * d)
    if 4 * t * d * itemsize + fixed > _VMEM_LIMIT_BYTES:
        max_t = (_VMEM_LIMIT_BYTES - fixed) // (4 * d * itemsize)
        raise ValueError(
            f"flash_attention: {orig_t} tokens (padded {t}) at head_dim "
            f"{orig_d} (padded {d}) {q.dtype} need "
            f"{4 * t * d * itemsize + fixed} bytes of VMEM for the "
            f"whole-sequence K/V blocks, over the kernel's "
            f"{_VMEM_LIMIT_BYTES}-byte budget; the limit at this width and "
            f"dtype is {max_t // block * block} tokens")
    bh = b * h
    qf = q.reshape(bh, t, d)
    kf = k.reshape(bh, t, d)
    vf = v.reshape(bh, t, d)

    grid = (bh, t // block_q)
    kernel = functools.partial(_fa_kernel, block_k=block_k, causal=causal,
                               scale=scale, seq_len=t, kv_len=orig_t)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, t, d)
    return out[:, :, :orig_t, :orig_d]


def _reference(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=None):
    """Blockwise attention, (B, H, T, D) → (B, H, T, D).

    ``interpret=None`` auto-selects: the pallas interpreter on the CPU
    backend (the tests), the compiled kernel everywhere else — a kernel the
    chip's compiler refuses raises, it is never swapped for the dense
    reference.  T is padded to the block size and D to 128 lanes
    internally; sequences past the kernel's VMEM budget raise ``ValueError``.
    """
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _fa_forward(q, k, v, causal, scale_v, block_q, block_k, interpret)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    scale_v = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    _, vjp = jax.vjp(lambda q_, k_, v_: _reference(q_, k_, v_, causal,
                                                   scale_v), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
