"""Expert parallelism — top-1 MoE dispatch over an ``ep`` mesh axis.

Absent in the reference (SURVEY.md §2.3: "no MoE ops"); TPU-first design:
one expert per device along ``ep``, tokens routed by a learned gate,
exchanged with two ``lax.all_to_all`` collectives (dispatch + combine) —
the canonical GShard/Switch layout.  Capacity-bounded with dropped-token
semantics (dropped tokens pass through with zero expert contribution), all
static shapes, differentiable end-to-end.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis import divergence as _div
from ..analysis import sanitizer as _san

__all__ = ["moe_layer", "switch_moe_local"]


def switch_moe_local(expert_fn, params, x, axis_name, capacity):
    """Per-device body (inside shard_map): x (T_local, D) → (T_local, D).

    ``params``: {"gate": (D, E) replicated, "expert": pytree with leading
    ep-sharded axis (this device's expert after squeeze)}.
    """
    E = lax.psum(1, axis_name)
    d = x.shape[-1]
    expert_params = jax.tree.map(lambda p: p[0], params["expert"])

    logits = x @ params["gate"]                       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    eidx = jnp.argmax(probs, axis=-1)                 # (T,)
    gate = jnp.max(probs, axis=-1)                    # (T,)

    onehot = jax.nn.one_hot(eidx, E, dtype=x.dtype)   # (T, E)
    # position of each token within its expert's bucket (0-based)
    pos_in_e = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot,
                       axis=-1).astype(jnp.int32)
    keep = pos_in_e < capacity
    slot = jnp.clip(pos_in_e, 0, capacity - 1)

    # dispatch buffer: (E, C, D); dropped tokens contribute nothing
    disp = jnp.zeros((E, capacity, d), x.dtype)
    disp = disp.at[eidx, slot].add(x * keep[:, None].astype(x.dtype))
    # exchange: row e of every device lands on device e
    recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                 # (E, C, D) from sources
    out = expert_fn(expert_params, recv.reshape(E * capacity, d))
    out = out.reshape(E, capacity, d)
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                 # (E, C, D) per expert
    y = back[eidx, slot] * (gate * keep.astype(gate.dtype))[:, None]
    return y


def moe_layer(expert_fn, gate_w, expert_params, x, mesh, ep_axis="ep",
              capacity_factor=1.25):
    """SPMD entry: x (B, D) sharded over ``ep`` (token-parallel), experts
    sharded one-per-device; returns (B, D) with the same sharding."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if _san.collectives:
        _div.record("moe.all_to_all", axis=ep_axis, shape=tuple(x.shape),
                    dtype=getattr(x, "dtype", None),
                    site="parallel.moe.moe_layer")
    E = mesh.shape[ep_axis]
    assert gate_w.shape[-1] == E, \
        f"gate width {gate_w.shape[-1]} != ep axis size {E} (one expert " \
        "per device: tokens routed past the mesh would silently misroute)"
    for leaf in jax.tree.leaves(expert_params):
        assert leaf.shape[0] == E, \
            f"expert param leading axis {leaf.shape[0]} != ep axis size {E}"
    b = x.shape[0]
    t_local = b // E
    capacity = max(1, math.ceil(t_local / E * capacity_factor))

    fn = functools.partial(switch_moe_local, expert_fn, axis_name=ep_axis,
                           capacity=capacity)
    params = {"gate": gate_w, "expert": expert_params}
    param_specs = {"gate": P(),
                   "expert": jax.tree.map(lambda _: P(ep_axis),
                                          expert_params)}
    return shard_map(
        lambda p, xx: fn(p, xx),
        mesh=mesh,
        in_specs=(param_specs, P(ep_axis)),
        out_specs=P(ep_axis),
    )(params, x)
