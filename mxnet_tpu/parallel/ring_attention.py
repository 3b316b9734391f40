"""Ring attention — sequence/context parallelism over an ICI ring.

The reference has **no** long-context machinery (SURVEY.md §5.7: nothing
beyond bucketing and fused RNN); this module is the TPU-native capability
designed fresh for it.  Sequence length is sharded over a mesh axis (``sp``):
each device keeps its local Q chunk resident and the K/V chunks rotate around
the ring via ``lax.ppermute`` — one neighbor hop per step, so communication
rides nearest-neighbor ICI links and overlaps with the local block matmuls
(the collective-matmul pattern).  Softmax is computed online/blockwise
(flash-attention style running max/denominator), so the full ``T×T`` score
matrix never materializes and memory stays O(T_local × head_dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ring_self_attention",
           "blockwise_attention_reference"]

_NEG = -1e30


def blockwise_attention_reference(q, k, v, causal=False, scale=None):
    """Plain full-materialization attention (B, H, T, D) — the numerical
    reference the ring kernel is tested against."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Per-device body: call inside ``shard_map`` with Q/K/V sharded on the
    sequence axis. Shapes (B, H, T_local, D).

    Online-softmax accumulation across ring steps:
      m — running row max, l — running denominator, o — unnormalized output.
    Each step processes the K/V chunk currently resident, then rotates it one
    hop (device i receives from i+1, so after step s the resident chunk
    originated at device (i+s) mod n — used for causal position offsets).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t, d = q.shape[-2], q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    q_pos = idx * t + jnp.arange(t)  # global positions of local queries

    # derive the accumulators from q so they carry q's varying-axes type —
    # fresh jnp.zeros would be "replicated" and fail shard_map's vma check
    # when fed through the ppermute-ing loop carry.
    zrow = (q[..., :1] * 0).astype(jnp.float32)
    m0 = zrow + _NEG
    l0 = zrow
    o0 = (q * 0).astype(jnp.float32)
    perm = [(j, (j - 1) % n) for j in range(n)]

    def body(step, carry):
        m, l, o, kc, vc = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            src = (idx + step) % n
            k_pos = src * kc.shape[2] + jnp.arange(kc.shape[2])
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        new_o = o * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        # rotate K/V one hop; the last rotation is redundant but keeps the
        # loop shape static for lax.fori_loop.
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return new_m, new_l, new_o, kc, vc

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_self_attention(q, k, v, mesh, sp_axis="sp", dp_axis="dp",
                        causal=False, scale=None):
    """SPMD entry point: (B, H, T, D) arrays, T sharded over ``sp`` and B
    over ``dp``.  Returns attention output with the same sharding."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = P(dp_axis, None, sp_axis, None)
    fn = functools.partial(ring_attention, axis_name=sp_axis, causal=causal,
                           scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
