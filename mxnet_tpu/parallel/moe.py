"""Expert parallelism.

Two layers live here.  :func:`moe_layer` / :func:`switch_moe_local` (below)
are the top-1, one-expert-per-device Switch layout with its two
``all_to_all`` exchanges.  :func:`routed_expert_share` is **one chip's share
of an expert-parallel layer**: the chip is told which experts it holds, routes
every token over ALL the experts at the published router width, and computes
the part of the layer's result that its own experts give — grouped matrix
products over the assignments sorted by expert, no capacity, no dropped
token.  On one chip it runs without its exchange (no code stands in for the
absent chips); summed over every chip's share it is the whole layer
(``tests/test_latent_moe.py`` holds it to that).

Top-1 MoE dispatch over an ``ep`` mesh axis:

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

__all__ = ["moe_layer", "switch_moe_local", "group_limited_topk",
           "routed_expert_share",
           "routed_expert_share_by_expert"]


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


# --------------------------------------------------------------------------
# one chip's share of a routed-expert layer (DeepSeek-V3 family routing)

def group_limited_topk(scores, top_k, n_group, topk_group):
    """The family's group-limited choice over router ``scores (T, E)``
    (float32, every expert's): experts lie in ``n_group`` groups of
    consecutive ids, a group scores the sum of its two largest, the
    ``topk_group`` best groups stay and the ``top_k`` largest scores among
    them are chosen.  Returns ``(ids (T, top_k) int32, scores (T, top_k))``
    — the raw scores of the chosen, not yet normalised."""
    T, E = scores.shape
    if n_group > 1:
        grouped = scores.reshape(T, n_group, E // n_group)
        group_score = lax.top_k(grouped, 2)[0].sum(-1)
        _, keep = lax.top_k(group_score, topk_group)
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        scores = jnp.where(jnp.repeat(kept, E // n_group, axis=1),
                           scores, -1.0)
    chosen, ids = lax.top_k(scores, top_k)
    return ids.astype(jnp.int32), chosen


def _grouped(x, w, sizes):
    """``x (M, K)`` rows sorted by group times ``w (G, K, N)``: one grouped
    (ragged) product, each group's weights read at most once and no row
    multiplied by another group's.  Products in the weights' dtype
    (bfloat16 on the MXU, float32 accumulation); float32 weights take the
    highest precision."""
    return lax.ragged_dot(
        x.astype(w.dtype), w, sizes, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if w.dtype == jnp.float32 else None)


def route_to_held(x, router_w, held, *, top_k, n_group=1, topk_group=1,
                  scale=1.0, valid=None, select_bias=None):
    """The routing of ``x (T, D)`` float32 as this chip sees it; call it
    under the named scope ``moe.route``.  Scores are ``sigmoid(x router_w)``
    over the WHOLE router ``(D, E)`` in float32 at the highest precision,
    the choice is :func:`group_limited_topk`, the weights ``scale * s_k /
    sum_chosen s`` — all independent of ``held``, the tuple of global ids of
    the ``G`` experts held here.  ``valid (T,) bool`` marks real rows;
    padding is routed nowhere.  ``select_bias (E,)`` float32 (the family's
    ``noaux_tc`` score correction) joins the scores for the CHOICE only:
    the experts are the ``top_k`` of ``s + select_bias``, their weights are
    still made of ``s``.

    Returns ``(local (T, top_k) int32, weights (T, top_k), assignments
    int32)``: each choice's position in ``held`` (``G`` where it is held
    elsewhere or the row is padding) with its weight, and the assignments
    made over all ``E``."""
    import numpy as np
    T = x.shape[0]
    E = router_w.shape[1]
    G = len(held)
    scores = jax.nn.sigmoid(jnp.dot(
        x, router_w, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    if select_bias is None:
        ids, chosen = group_limited_topk(scores, top_k, n_group, topk_group)
    else:
        ids, _ = group_limited_topk(scores + select_bias, top_k, n_group,
                                    topk_group)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    lookup = np.full((E,), G, "int32")
    lookup[np.asarray(held, "int64")] = np.arange(G, dtype="int32")
    local = jnp.asarray(lookup)[ids]              # G = held elsewhere
    if valid is None:
        n_assign = jnp.int32(T * top_k)
    else:
        local = jnp.where(valid[:, None], local, G)
        n_assign = valid.sum().astype(jnp.int32) * top_k
    return local, weights, n_assign


def rows_received(flat, G):
    """``(G,) int32``: the rows each held expert received, from the flat
    ``local`` of :func:`route_to_held`."""
    return (flat[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]
            ).sum(0).astype(jnp.int32)


def routed_expert_share(x, router_w, w_gate, w_up, w_down, held, *,
                        top_k, n_group=1, topk_group=1, scale=1.0,
                        valid=None, select_bias=None):
    """This chip's part of ``sum_k w_k E_k(x)`` for ``x (T, D)`` float32.

    ``router_w (D, E)`` float32 is the WHOLE router (``E`` = the published
    expert count); ``held`` is the tuple of global expert ids whose weights
    ``w_gate`` / ``w_up (G, D, F)`` and ``w_down (G, F, D)`` are, in that
    order.  The routing is :func:`route_to_held`'s (``select_bias`` is its
    argument).
    Only the chosen experts that are held are computed: the ``T * top_k``
    assignments are sorted by held expert (the others last), the first
    ``T * min(top_k, G)`` rows — every held assignment fits, so no token is
    ever dropped — go through three grouped products
    ``(silu(x Wg) * (x Wu)) Wd`` and are summed back onto their tokens.

    Returns ``(y (T, D) float32, rows (G,) int32, assignments int32)``:
    the partial result, the rows each held expert received, and the
    assignments made over all ``E`` experts."""
    T, D = x.shape
    G = len(held)
    with jax.named_scope("moe.route"):
        local, weights, n_assign = route_to_held(
            x, router_w, held, top_k=top_k, n_group=n_group,
            topk_group=topk_group, scale=scale, valid=valid,
            select_bias=select_bias)
        flat = local.reshape(-1)
        rows = rows_received(flat, G)
        M = T * min(top_k, G)
        order = jnp.argsort(flat, stable=True)[:M]
        token = order // top_k
        live = jnp.arange(M) < rows.sum()
    with jax.named_scope("moe.experts"):
        xs = x[token]
        hidden = jax.nn.silu(_grouped(xs, w_gate, rows)) \
            * _grouped(xs, w_up, rows)
        out = _grouped(hidden, w_down, rows)
        # rows past the held assignments belong to no group: whatever the
        # grouped product left there is not part of the result
        out = jnp.where(live[:, None],
                        out * weights.reshape(-1)[order][:, None], 0.0)
        y = jnp.zeros((T, D), jnp.float32).at[token].add(out)
    return y, rows, n_assign


def routed_expert_share_by_expert(x, router_w, w_gate, w_up, w_down, held, *,
                                  top_k, n_group=1, topk_group=1, scale=1.0,
                                  valid=None, select_bias=None):
    """:func:`routed_expert_share`'s contract and answer for FEW rows (a
    decode step's batch): the same routing, and the products as ONE DENSE
    CHAIN AN EXPERT over all ``T`` rows, ``(silu(x Wg) * (x Wu)) Wd`` times
    the expert's weight for each row (0 where the row did not choose it),
    under a conditional that skips an expert no row chose, so that its
    weights are not read.  At a step's few rows the chip's grouped product
    does not stream (it read a third of the bandwidth at 11 rows and 4
    experts hit a layer, ``PERF.md``, PR 37) where a plain product by
    expert does; at a prefill's hundreds of rows every expert is hit and
    the grouped products, which multiply no row by an expert it did not
    choose, are the form to use."""
    G = len(held)
    with jax.named_scope("moe.route"):
        local, weights, n_assign = route_to_held(
            x, router_w, held, top_k=top_k, n_group=n_group,
            topk_group=topk_group, scale=scale, valid=valid,
            select_bias=select_bias)
        rows = rows_received(local.reshape(-1), G)
        # (T, G): each held expert's weight for each row
        coef = ((local[:, :, None] == jnp.arange(G, dtype=jnp.int32))
                * weights[:, :, None]).sum(1)

    def dot(a, w):
        return jnp.dot(a.astype(w.dtype), w,
                       preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGHEST
                       if w.dtype == jnp.float32 else None)

    def one(g):
        hidden = jax.nn.silu(dot(x, w_gate[g])) * dot(x, w_up[g])
        return dot(hidden, w_down[g]) * coef[:, g:g + 1]

    with jax.named_scope("moe.experts"):
        y = jnp.zeros((x.shape[0], w_down.shape[-1]), jnp.float32)
        for g in range(G):
            y = y + lax.cond(rows[g] > 0, lambda g=g: one(g),
                             lambda: jnp.zeros_like(y))
    return y, rows, n_assign

