"""The gated delta rule with a decay a key channel ("KDA", the Kimi / Solar
Open 2 family's linear-attention layer) as pure functions of arrays.

One head keeps a matrix state ``S (dk, dv)`` (zero at the start) and, a
token at a time, with ``q_t``, ``k_t (dk,)``, ``v_t (dv,)``, a log-decay
``g_t (dk,) <= 0`` a CHANNEL of the key and a step ``beta_t`` a head,

    S <- diag(exp(g_t)) S                    every key channel forgets alone
    u  = beta_t (v_t - S^T k_t)              what the state lacks of v_t
    S <- S + k_t u^T                         a rank-one correction
    o_t = S^T q_t

With ``beta`` in (0, 2) the transition ``I - beta k k^T`` (``|k| = 1``) has
an eigenvalue in (-1, 1): the family's ``allow_neg_eigval``.  Three forms of
the same recurrence live here:

- :func:`delta_rule_sequential` — the definition, a ``lax.scan`` over tokens
  (what the tests hold the other two to);
- :func:`delta_rule_step` — one token a row on a resident state (decode);
- :func:`delta_rule_chunked` — the prefill's form, the sequence in chunks of
  ``chunk`` tokens.  Inside a chunk, with ``G`` the running sum of ``g`` and
  ``S0`` the state the chunk inherits, the corrections ``U`` solve ``(I + A)
  U = beta (V - (e^G K) S0)`` with the strictly lower-triangular ``A[i, j] =
  beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])``; then ``O = (e^G Q) S0 +
  B U`` with ``B[i, j] = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])`` for ``j
  <= i``, and the chunk leaves ``diag(e^(G_last)) S0 + (K e^(G_last -
  G))^T U``.  **No exponent is ever positive**: ``A`` and ``B`` are formed by
  sub-chunks, a block below the diagonal as one product of ``k_i e^(G_i -
  G_r)`` and ``k_j e^(G_r - G_j)`` with ``r`` the last row before the block
  of ``i`` (``j <= r < i``), a block on the diagonal entry by entry under
  the mask; a naive ``e^(G_i) e^(-G_j)`` overflows once a channel has
  forgotten 88 nats inside a chunk.  ``T = (I + A)^-1`` is by forward
  substitution, a row at a time.  What does not depend on ``S0`` (``T``,
  ``T (beta V)``, ``T (beta e^G K)``, ``B``) is formed for all chunks at
  once; the ``lax.scan`` over chunks carries the state through four
  products a chunk.  A position whose ``beta`` and ``g`` are 0 neither
  decays the state nor feeds it, so padding behind a sequence's true length
  leaves the returned state as of that length.

``G``, ``A``, ``T``, the decay and the state are float32 whatever the
products' dtype; the ``(chunk, d)`` products run in ``dtype`` (bfloat16 on
the MXU) with float32 accumulation.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .ssm import _einsum

__all__ = ["delta_rule_sequential", "delta_rule_step", "delta_rule_chunked"]


def delta_rule_step(state, q, k, v, g, beta):
    """One token a row: ``state (b, H, dk, dv)`` float32, ``q``, ``k``, ``g
    (b, H, dk)``, ``v (b, H, dv)``, ``beta (b, H)``.  Returns ``(new state,
    o (b, H, dv))``, float32."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    state = jnp.exp(g)[..., None] * state
    u = beta[..., None] * (v - (state * k[..., None]).sum(-2))
    state = state + k[..., None] * u[..., None, :]
    return state, (state * q[..., None]).sum(-2)


def delta_rule_sequential(q, k, v, g, beta, state=None):
    """The recurrence, token by token.  ``q``, ``k``, ``g (b, L, H, dk)``,
    ``v (b, L, H, dv)``, ``beta (b, L, H)``; ``state (b, H, dk, dv)`` or None
    for zeros.  Returns ``(o (b, L, H, dv), state)``, float32."""
    b, _L, H, dk = q.shape
    if state is None:
        state = jnp.zeros((b, H, dk, v.shape[-1]), jnp.float32)
    state, o = lax.scan(lambda S, t: delta_rule_step(S, *t), state,
                        tuple(jnp.moveaxis(x, 1, 0)
                              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _decayed_products(left, k, G, sub, dtype, inclusive):
    """``M[i, j] = sum_c left_i[c] k_j[c] exp(G_i[c] - G_j[c])`` inside each
    chunk for ``j < i`` (``j <= i`` where ``inclusive``), zero elsewhere:
    ``left``, ``k``, ``G (..., C, d)`` give ``(..., C, C)`` float32.  By
    sub-chunks of ``sub`` rows, so that every exponent is <= 0 and no ``(C,
    C, d)`` array is kept (module docstring)."""
    C = G.shape[-2]
    rows = []
    for a in range(0, C, sub):
        hi = min(a + sub, C)
        Gi, li = G[..., a:hi, :], left[..., a:hi, :]
        # the diagonal block, entry by entry: masked BEFORE the exponential
        # (above the diagonal the difference is positive)
        i, j = jnp.arange(a, hi)[:, None], jnp.arange(a, hi)[None, :]
        seg = jnp.where((j <= i if inclusive else j < i)[..., None],
                        Gi[..., :, None, :] - Gi[..., None, :, :], -jnp.inf)
        diag = (li[..., :, None, :] * k[..., None, a:hi, :]
                * jnp.exp(seg)).sum(-1)
        parts = [diag, jnp.zeros(diag.shape[:-1] + (C - hi,), jnp.float32)]
        if a:
            # the blocks below it, through the last row before this block:
            # i > r >= j, both exponents <= 0
            Gr = G[..., a - 1:a, :]
            parts.insert(0, _einsum(
                "...ic,...jc->...ij", li * jnp.exp(Gi - Gr),
                k[..., :a, :] * jnp.exp(Gr - G[..., :a, :]), dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A (..., C, C)`` by
    forward substitution: row ``i`` of the inverse is ``e_i - sum_{j<i} A[i,
    j] row_j``.  float32 on the vector unit."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=jnp.float32)

    def row(i, T):
        new = eye[i] - (A[..., i, :, None] * T).sum(-2)
        return lax.dynamic_update_index_in_dim(T, new, i, axis=-2)

    # rows not yet reached are zero, so the sum over all j sees only j < i
    return lax.fori_loop(0, C, row, jnp.zeros_like(A))


def delta_rule_chunked(q, k, v, g, beta, chunk=64, sub=16, dtype="float32"):
    """The recurrence from a zero state over ``L`` tokens, in chunks (a last
    chunk that is not whole is padded with ``beta`` = ``g`` = 0).  Arguments
    as :func:`delta_rule_sequential`; ``dtype`` is what the ``(chunk, d)``
    products run in.  Returns ``(o (b, L, H, dv), state (b, H, dk, dv))``:
    ``state`` is the state after the last position whose ``beta`` or ``g``
    is not 0.  The module docstring has the algebra."""
    b, L0, H, dk = q.shape
    dv = v.shape[-1]
    C = min(int(chunk), L0)
    if L0 % C:
        # whole chunks: the tail is padding, which beta = g = 0 makes inert
        pad = lambda x: jnp.pad(
            x, ((0, 0), (0, -L0 % C)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = pad(q), pad(k), pad(v), pad(g), pad(beta)
    n = q.shape[1] // C

    def chunks(x):
        """``(b, L, H, ...) -> (b, H, n, C, ...)``, float32."""
        x = x.astype(jnp.float32).reshape((b, n, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), \
        chunks(beta)
    G = jnp.cumsum(g, axis=-2)                          # (b, H, n, C, dk) <= 0
    bk = beta[..., None] * k
    A = _decayed_products(bk, k, G, sub, dtype, inclusive=False)
    Bm = _decayed_products(q, k, G, sub, dtype, inclusive=True)
    T = _unit_lower_inverse(A)
    decay = jnp.exp(G)
    Uv = _einsum("...ij,...jd->...id", T, beta[..., None] * v, dtype)
    W = _einsum("...ij,...jd->...id", T, bk * decay, dtype)
    qd = q * decay
    last = G[..., -1:, :]
    kd = k * jnp.exp(last - G)                          # to the chunk's end
    whole = jnp.exp(last[..., 0, :])                    # (b, H, n, dk)

    def one(S, t):
        Uv_c, W_c, qd_c, B_c, kd_c, whole_c = t
        U = Uv_c - _einsum("...ik,...kd->...id", W_c, S, dtype)
        o = _einsum("...ik,...kd->...id", qd_c, S, dtype) \
            + _einsum("...ij,...jd->...id", B_c, U, dtype)
        S = whole_c[..., None] * S \
            + _einsum("...ik,...id->...kd", kd_c, U, dtype)
        return S, o

    state, o = lax.scan(
        one, jnp.zeros((b, H, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (Uv, W, qd, Bm, kd, whole)))
    # (n, b, H, C, dv) -> (b, L, H, dv)
    o = jnp.moveaxis(o, 0, 2).reshape(b, H, n * C, dv)
    return jnp.moveaxis(o, 1, 2)[:, :L0], state
