"""Ulysses-style sequence parallelism — head-sharded attention via all_to_all.

Companion to :mod:`ring_attention` (SURVEY.md §5.7: both SP designs are
TPU-native additions; the reference has no long-context machinery).  Where
ring attention keeps the sequence sharded and rotates K/V around the ICI
ring, the Ulysses layout trades TWO ``all_to_all`` collectives for zero
inner-loop communication: activations arrive sequence-sharded
(B, T_local, H, D), an all_to_all re-shards them to head-sharded
(B, T, H/P, D), each device runs ordinary full-sequence attention for its
head group (one big MXU matmul chain, no masking subtleties across chunks),
and a second all_to_all restores sequence sharding.

Trade-off (How-to-Scale-Your-Model framing): ring = O(T²) compute overlap
with P nearest-neighbor hops, memory O(T_local·D); Ulysses = two all_to_alls
(which XLA lowers to balanced ICI traffic) but requires the axis size P to divide the head count and
materializes T globally per device — best for moderate T with many heads.
"""
from __future__ import annotations

import functools

from jax import lax

from .ring_attention import blockwise_attention_reference

__all__ = ["ulysses_attention", "ulysses_self_attention"]


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None):
    """Per-device body (inside ``shard_map``): Q/K/V (B, H, T_local, D) with
    the sequence axis sharded over ``axis_name``.  The axis size must divide
    the head count (each device takes H/P whole heads)."""
    def seq_to_heads(x):
        # (B, H, T_local, D) -> (B, H/P, T, D): scatter heads, gather seq
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    out = blockwise_attention_reference(qh, kh, vh, causal=causal,
                                        scale=scale)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_self_attention(q, k, v, mesh, sp_axis="sp", dp_axis="dp",
                           causal=False, scale=None):
    """SPMD entry point, drop-in alternative to ``ring_self_attention``:
    (B, H, T, D) arrays with T sharded over ``sp`` and B over ``dp``."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n_sp = mesh.shape[sp_axis]
    if q.shape[1] % n_sp != 0:
        raise ValueError(
            f"Ulysses SP needs heads ({q.shape[1]}) divisible by the sp axis "
            f"({n_sp}); use ring attention for few-head models")
    spec = P(dp_axis, None, sp_axis, None)
    fn = functools.partial(ulysses_attention, axis_name=sp_axis,
                           causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
