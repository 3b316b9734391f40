"""Mamba-2 (state-space duality) mathematics as pure functions of arrays.

One selective state-space head keeps a state ``S (P, N)`` (``P`` the head's
width, ``N`` the state size) and, a token at a time,

    a_t = exp(dt_t * A)                      (A < 0: a decay in (0, 1))
    S_t = a_t * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

with ``x_t (P,)`` the head's input, ``B_t``, ``C_t (N,)`` shared by the heads
of a *group* (head ``h`` reads group ``h // (H / G)``), and ``dt_t`` the
head's softplus-ed step.  Three forms of the same recurrence live here:

- :func:`ssm_scan_sequential` — the definition, a ``lax.scan`` over tokens
  (what the tests hold the other two to);
- :func:`ssm_scan_chunked` — the prefill's form: the sequence in chunks of
  ``chunk`` tokens, inside a chunk two matrix products on the MXU (``C B^T``
  masked by the decay between positions, times ``dt x``), between chunks a
  carried state.  A position whose ``dt`` is 0 neither decays nor feeds the
  state, so padding behind a sequence's true length leaves the returned
  state as of that length;
- :func:`ssm_step` — one token a row on a resident state (decode).

Around them: the depthwise causal convolution in its two forms
(:func:`causal_conv`, :func:`conv_step`, with :func:`conv_tail` the last
``K - 1`` real inputs a prefill hands the step) and the gated group RMS norm
(:func:`gated_group_norm`).  Decay, ``dt`` and the state are float32
whatever the products' dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssm_scan_sequential", "ssm_scan_chunked", "ssm_step",
           "causal_conv", "conv_step", "conv_tail", "gated_group_norm",
           "heads_from_groups"]


def heads_from_groups(v, num_heads):
    """``v (..., G, N)`` as each head reads it, ``(..., H, N)``: head ``h``
    uses group ``h // (H // G)``."""
    G = v.shape[-2]
    if G == num_heads:
        return v
    return jnp.repeat(v, num_heads // G, axis=-2)


def _einsum(spec, a, b, dtype):
    """Product in ``dtype`` (bfloat16 on the MXU), float32 out; float32
    operands take the highest precision."""
    dtype = jnp.dtype(dtype)
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=lax.Precision.HIGHEST
                      if dtype == jnp.float32 else None)


def ssm_scan_sequential(x, dt, A, B, C, D, state=None):
    """The recurrence, token by token.  ``x (b, L, H, P)``, ``dt (b, L, H)``
    (already softplus-ed; 0 at padding), ``A (H,)`` negative, ``B``, ``C
    (b, L, G, N)``, ``D (H,)``; ``state (b, H, P, N)`` or None for zeros.
    Returns ``(y (b, L, H, P), state (b, H, P, N))``, float32."""
    b, _L, H, P = x.shape
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = heads_from_groups(B.astype(jnp.float32), H)
    Ch = heads_from_groups(C.astype(jnp.float32), H)
    if state is None:
        state = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)

    state, y = lax.scan(
        lambda S, t: ssm_step(S, t[0], t[1], A, t[2], t[3], D), state, (
        jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0)))
    return jnp.moveaxis(y, 0, 1), state


def ssm_step(state, x, dt, A, B, C, D):
    """One token a row: ``state (b, H, P, N)`` float32, ``x (b, H, P)``,
    ``dt (b, H)``, ``B``, ``C (b, G, N)`` or already ``(b, H, N)``.  Returns
    ``(new state, y (b, H, P))``."""
    H = x.shape[-2]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = heads_from_groups(B.astype(jnp.float32), H)
    Ch = heads_from_groups(C.astype(jnp.float32), H)
    decay = jnp.exp(dt * A)
    state = decay[..., None, None] * state \
        + (dt[..., None] * x)[..., None] * Bh[..., None, :]
    y = (state * Ch[..., None, :]).sum(-1) + D[:, None] * x
    return state, y


def ssm_scan_chunked(x, dt, A, B, C, D, chunk=128, dtype="float32"):
    """The recurrence from a zero state over ``L`` tokens, in chunks (a
    last chunk that is not whole is padded with ``dt`` = 0).  Arguments as
    :func:`ssm_scan_sequential`; ``dtype`` is what the within-chunk products
    run in.  Returns ``(y (b, L, H, P), state (b, H, P, N))``: ``state`` is
    the state after the last position whose ``dt`` is not 0.

    Within a chunk, for positions ``s <= l``: ``y_l = sum_s (C_l . B_s)
    exp(cum_l - cum_s) dt_s x_s`` with ``cum`` the running sum of ``dt A``
    inside the chunk (every exponent is <= 0: nothing overflows); the state
    a chunk adds is ``sum_s exp(cum_last - cum_s) dt_s x_s (outer) B_s``;
    the state it inherits decays by ``exp(cum_l)`` on its way to ``y_l``."""
    b, L0, H, P = x.shape
    G, N = B.shape[-2:]
    Q = min(int(chunk), L0)
    if L0 % Q:
        # whole chunks: the tail is padding, which dt = 0 makes inert
        pad = lambda v: jnp.pad(
            v, ((0, 0), (0, -L0 % Q)) + ((0, 0),) * (v.ndim - 2))
        x, dt, B, C = pad(x), pad(dt), pad(B), pad(C)
    L = x.shape[1]
    c = L // Q
    x = x.astype(jnp.float32).reshape(b, c, Q, H, P)
    dt = dt.astype(jnp.float32).reshape(b, c, Q, H)
    Bc = B.astype(jnp.float32).reshape(b, c, Q, G, N)
    Cc = C.astype(jnp.float32).reshape(b, c, Q, G, N)
    cum = jnp.cumsum(dt * A, axis=2)                    # (b, c, Q, H) <= 0
    xdt = x * dt[..., None]
    # within a chunk: scores by group, decay by head
    cb = _einsum("bclgn,bcsgn->bcgls", Cc, Bc, dtype)   # (b, c, G, Q, Q)
    cb = jnp.repeat(cb, H // G, axis=2)                 # (b, c, H, Q, Q)
    cum_h = jnp.moveaxis(cum, 3, 2)                     # (b, c, H, Q)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # masked BEFORE the exponential: above the diagonal the difference is
    # positive and would overflow
    seg = jnp.where(causal, cum_h[..., :, None] - cum_h[..., None, :],
                    -jnp.inf)
    y = _einsum("bchls,bcshp->bclhp", cb * jnp.exp(seg), xdt, dtype)
    # what each chunk adds to the state, and the state each chunk inherits
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (b, c, Q, H)
    Bh = jnp.repeat(Bc, H // G, axis=3)                 # (b, c, Q, H, N)
    added = _einsum("bcshp,bcshn->bchpn", xdt * to_end[..., None], Bh,
                    dtype)
    whole = jnp.exp(cum[:, :, -1, :])                   # (b, c, H)

    def carry(S, t):
        add, dec = t
        return dec[..., None, None] * S + add, S

    state, inherited = lax.scan(
        carry, jnp.zeros((b, H, P, N), jnp.float32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    inherited = jnp.moveaxis(inherited, 0, 1)           # (b, c, H, P, N)
    Ch = jnp.repeat(Cc, H // G, axis=3)                 # (b, c, Q, H, N)
    y = y + _einsum("bclhn,bchpn->bclhp", Ch, inherited, dtype) \
        * jnp.exp(cum)[..., None]
    y = y + D[:, None] * x
    return y.reshape(b, L, H, P)[:, :L0], state


def causal_conv(u, w, bias):
    """Depthwise causal convolution + SiLU over a whole sequence: ``u (b, L,
    C)``, ``w (C, K)``, ``bias (C,)``; ``out_t = silu(bias + sum_j w[:, j]
    u_{t-(K-1)+j})`` with zeros before the sequence.  float32."""
    K = w.shape[1]
    u = u.astype(jnp.float32)
    L = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(K):
        out = out + padded[:, j:j + L] * w[:, j].astype(jnp.float32)
    return jax.nn.silu(out)


def conv_tail(u, lengths, K):
    """The last ``K - 1`` real inputs of each row, oldest first: ``u (b, L,
    C)`` and ``lengths (b,)`` give ``(b, K - 1, C)`` — positions ``lengths -
    (K - 1) .. lengths - 1``, zeros where that is before the sequence."""
    pos = lengths[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
    got = jnp.take_along_axis(u, jnp.maximum(pos, 0)[..., None], axis=1)
    return jnp.where((pos >= 0)[..., None], got, jnp.zeros((), u.dtype))


def conv_step(tail, u, w, bias):
    """One token a row: ``tail (b, K - 1, C)`` (oldest first), the new input
    ``u (b, C)``.  Returns ``(new tail, out (b, C))`` — the new tail in the
    old one's dtype."""
    window = jnp.concatenate(
        [tail.astype(jnp.float32), u.astype(jnp.float32)[:, None]], axis=1)
    out = bias.astype(jnp.float32) + jnp.einsum(
        "bkc,ck->bc", window, w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    return window[:, 1:].astype(tail.dtype), jax.nn.silu(out)


def gated_group_norm(y, z, gain, groups, eps):
    """``v = y * silu(z)``, RMS-normalised inside each of ``groups`` equal
    slices of the last axis, times ``gain`` (as wide as the axis).
    float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = v.shape
    g = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    return g.reshape(shape) * gain
