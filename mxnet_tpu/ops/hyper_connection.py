"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880; hyper-
connections, arXiv:2409.19606) as pure functions of arrays: the residual
path of a block whose hidden state is ``n`` streams.

A token's stream is ``X (n, C)`` float32.  A sublayer ``f`` with its own
``phi (nC, n + n + n*n)`` (columns: pre | post | res), ``b (n + n + n*n,)``
and scalars ``a = (a_pre, a_post, a_res)`` computes

    x  = vec(X)                    x' = x * rsqrt(mean(x^2) + eps)
    Hpre  = sigmoid(a_pre  * (x' phi_pre)  + b_pre)            (n,)
    Hpost = 2 sigmoid(a_post * (x' phi_post) + b_post)         (n,)
    M = exp(clip(a_res * mat(x' phi_res) + b_res, lo, hi))     (n, n)
    iters times:  M = M / (colsum(M) + eps);  M = M / (rowsum(M) + eps)
    u  = Hpre X                    the sublayer's input        (C,)
    X' = M X + Hpost^T f(u)        the stream it leaves        (n, C)

so every sublayer reads a learned mixture of the streams, writes its output
back to all of them with learned weights, and mixes the streams among
themselves by a matrix that 20 Sinkhorn-Knopp rounds make doubly stochastic
(row and column sums 1: the mix neither amplifies nor loses the signal
whatever the depth).  The flattened stream's norm has no gain (a gain folds
into ``phi``).

This module is the DEFINITION, and what a program lowered for the CPU runs.
Where a program is lowered for the chip, a sublayer's mixing is two kernels,
``ops.pallas_kernels.hc_pre`` (coefficients, every round, the read) and
``hc_post`` (the write-back), behind ``ops.pallas_kernels.by_platform``
(``serving.decode.latent_moe.LatentMoELM._sublayer`` is the caller); the
two halves hand a token's coefficients over as one lane tile
(:func:`coef_tile`, :func:`coef_parts`).

Everything here is ``jax.numpy`` in float32 over leading axes of any shape
(a decode step's ``(B,)``, a prefill's ``(B, S)``).  The ``x' phi`` product
runs at the highest precision: it has ``n (n + 2)`` columns, so its cost is
reading ``X``, not the product.  The rounds run as a loop on the device over
the ``n x n`` entries, each a vector over the tokens, so that a round is
some ``4 n^2`` vector operations and not two reductions over 4 lanes.  Named
scopes: ``hc.coef`` (norm, projection, sigmoids), ``hc.sinkhorn``,
``hc.mix`` (read and write-back).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["hc_coefficients", "sinkhorn", "hc_read", "hc_write",
           "sinkhorn_residual", "coef_tile", "coef_parts", "COEF_LANES"]

#: a token's coefficients between a sublayer's two halves: one lane tile
COEF_LANES = 128


def sinkhorn(m, iters, eps):
    """``iters`` Sinkhorn-Knopp rounds on positive ``m (..., n, n)``: the
    columns are normalised first (each entry over its column's sum + eps),
    then the rows."""
    shape, n = m.shape, m.shape[-1]
    flat = m.reshape((-1, n * n))

    def one_round(_, e):
        # entry by entry, every sum written out: same-shaped elementwise
        # operations, which the chip's compiler fuses into two loops a
        # round; a reduction over a 4-wide axis is a fusion of its own
        col = [sum(e[i * n + j] for i in range(n)) + eps for j in range(n)]
        e = [e[i * n + j] / col[j] for i in range(n) for j in range(n)]
        row = [sum(e[i * n + j] for j in range(n)) + eps for i in range(n)]
        return tuple(e[i * n + j] / row[i]
                     for i in range(n) for j in range(n))

    # a loop on the device, not 20 copies of the round: unrolled, 80
    # sublayers cost 2.6 s of compiling each and twelve minutes a 2,048-token
    # prefill program (sandbox compiles for the described chip)
    e = lax.fori_loop(0, int(iters), one_round,
                      tuple(flat[:, k] for k in range(n * n)))
    return jnp.stack(e, axis=-1).reshape(shape)


def hc_coefficients(X, params, iters, eps, clamp):
    """The three coefficient sets of one sublayer for streams ``X (..., n,
    C)``: ``(Hpre (..., n), Hpost (..., n), Hres (..., n, n))`` float32.
    ``params`` is ``{"phi": (nC, n (n + 2)), "a": (3,), "b": (n (n + 2),)}``
    float32, columns ordered pre | post | res (``res`` row-major: entry
    ``i, j`` weighs stream ``j`` in new stream ``i``); ``clamp = (lo, hi)``
    bounds the residual logits before the exponential."""
    lead, (n, C) = X.shape[:-2], X.shape[-2:]
    X = X.astype(jnp.float32)
    with jax.named_scope("hc.coef"):
        x = X.reshape(lead + (n * C,))
        inv = lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
        # (x * inv) phi = (x phi) * inv: the norm costs no second pass
        z = jnp.dot(x, params["phi"], precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32) * inv
        a = params["a"]
        z = z * jnp.concatenate([jnp.broadcast_to(a[k], (w,)) for k, w in
                                 enumerate((n, n, n * n))]) + params["b"]
        h_pre = jax.nn.sigmoid(z[..., :n])
        h_post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
        m = jnp.exp(jnp.clip(z[..., 2 * n:], clamp[0], clamp[1]))
    with jax.named_scope("hc.sinkhorn"):
        h_res = sinkhorn(m.reshape(lead + (n, n)), iters, eps)
    return h_pre, h_post, h_res


def hc_read(X, h_pre):
    """The sublayer's input ``u (..., C) = Hpre X``."""
    with jax.named_scope("hc.mix"):
        return sum(h_pre[..., j, None] * X[..., j, :]
                   for j in range(X.shape[-2]))


def hc_write(X, h_res, h_post, y):
    """The stream a sublayer leaves: ``X'[i] = sum_j Hres[i, j] X[j] +
    Hpost[i] y`` for its output ``y (..., C)``."""
    n = X.shape[-2]
    with jax.named_scope("hc.mix"):
        return jnp.stack(
            [sum(h_res[..., i, j, None] * X[..., j, :] for j in range(n))
             + h_post[..., i, None] * y for i in range(n)], axis=-2)


def sinkhorn_residual(h_res):
    """How far ``Hres (..., n, n)`` is from doubly stochastic: the largest
    ``|rowsum - 1|`` or ``|colsum - 1|`` of each matrix, ``(...,)``."""
    return jnp.maximum(jnp.abs(h_res.sum(-1) - 1.0).max(-1),
                       jnp.abs(h_res.sum(-2) - 1.0).max(-1))


def coef_tile(h_pre, h_post, h_res):
    """The coefficients of ``T`` tokens as ``(T, COEF_LANES)``: a token's
    ``Hpre | Hpost | Hres`` (row-major) in its first ``n (n + 2)`` lanes,
    zeros after: what ``ops.pallas_kernels.hc_pre`` writes and ``hc_post``
    reads."""
    n = h_pre.shape[-1]
    flat = jnp.concatenate([h_pre.reshape((-1, n)), h_post.reshape((-1, n)),
                            h_res.reshape((-1, n * n))], axis=-1)
    return jnp.pad(flat, ((0, 0), (0, COEF_LANES - n * (n + 2))))


def coef_parts(coef, lead, n):
    """``(Hpre (..., n), Hpost (..., n), Hres (..., n, n))`` of a
    :func:`coef_tile` over the leading axes ``lead``."""
    return (coef[:, :n].reshape(lead + (n,)),
            coef[:, n:2 * n].reshape(lead + (n,)),
            coef[:, 2 * n:n * (n + 2)].reshape(lead + (n, n)))
