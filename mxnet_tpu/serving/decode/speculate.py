"""Speculative-decoding drafters — propose k tokens, verify in ONE step.

Classic autoregressive decode pays one full forward step per token.
Speculative decoding breaks the serialization: a cheap *drafter* proposes
``k`` continuation tokens and the target model scores all of them in a
single fused **verify** program (:meth:`DecodeRuntime.verify`) — the
accepted prefix commits ``m + 1`` tokens per step (the ``m`` matching
drafts plus the target's own sample at the first mismatch, or a *bonus*
token when everything matched) for the price of roughly one.

**Deterministic acceptance.**  This implementation does not use the
stochastic accept/reject of Leviathan-style speculative *sampling*.  The
verify program computes, per drafted position, the token the target model
WOULD have sampled anyway — same logits (causal-mask-extended paged
attention is bitwise the step program's math, by induction over offsets
and layers), same per-request ``fold_in(key, step_idx + j)`` Gumbel
stream — and accepts a draft token iff it *equals* that sample.  The
emitted stream is therefore **always bitwise-identical to non-speculative
decode** — greedy and sampled alike, solo or continuous-batched,
regardless of what the drafter proposed or how ``spec_k`` adapted.  The
draft only ever changes *speed* (tokens per step), never a single bit of
output.  That is the whole determinism contract, and CI asserts it.

The drafter
-----------
:class:`NgramDrafter`
    Self-draft / prompt-lookup: find the most recent earlier occurrence
    of the context's own suffix n-gram and propose the tokens that
    followed it.  No extra model, no state, pure function of the
    request's committed tokens — ideal for repetitive or quoting
    workloads (code, retrieval, structured output).

A drafter (any :class:`Drafter`) is *fallible by design*: any drafter
error degrades the affected rows to non-speculative for that boundary —
requests never fail because a draft could not be produced.
"""
from __future__ import annotations

from collections import deque

import numpy as np

__all__ = ["Drafter", "NgramDrafter", "SpecState"]

_EMPTY = np.zeros((0,), "int32")


def _context(req):
    """A request's committed token stream: prompt + generated ids.
    Token ``i`` of this array sits at cache position ``i``."""
    if req.tokens:
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens, "int32")])
    return req.prompt


class SpecState:
    """Per-request speculative state: the adaptive ``spec_k`` plus the
    windowed acceptance history that drives it.  Adaptation reads only
    the request's OWN history, so it is a pure function of (prompt,
    seed, temperature) — solo and continuous runs adapt identically."""

    __slots__ = ("k", "k_max", "window")

    def __init__(self, k, k_max, window=16):
        self.k = int(k)
        self.k_max = int(k_max)
        self.window = deque(maxlen=int(window))

    def observe(self, proposed, accepted):
        """Record one verify round and adapt ``k``: grow on a hot window
        (>= 80% accepted), shrink on a cold one (< 30%)."""
        if proposed <= 0:
            return
        self.window.append((int(proposed), int(accepted)))
        prop = sum(p for p, _ in self.window)
        acc = sum(a for _, a in self.window)
        if len(self.window) < 4 or prop == 0:
            return
        rate = acc / prop
        if rate >= 0.8 and self.k < self.k_max:
            self.k += 1
        elif rate < 0.3 and self.k > 1:
            self.k -= 1

    @property
    def acceptance_rate(self):
        prop = sum(p for p, _ in self.window)
        if not prop:
            return 0.0
        return sum(a for _, a in self.window) / prop


class Drafter:
    """Base drafter.  The scheduler calls :meth:`bind` once at
    construction, :meth:`attach` / :meth:`detach` per request lifecycle,
    :meth:`propose_batch` per step boundary, and :meth:`observe` after
    each verify commits.  All hooks default to no-ops so a drafter only
    implements what it needs."""

    name = "drafter"

    def bind(self, runtime):
        """Called once with the target :class:`DecodeRuntime`."""

    def attach(self, req):
        """A request was admitted (its prompt K/V is, or is about to be,
        paged in).  May raise — the scheduler degrades that request to
        non-speculative."""

    def detach(self, req):
        """The request left the batch (finished, failed, aborted).  Must
        tolerate requests never attached."""

    def observe(self, req, proposed, accepted):
        """One verify round committed: ``accepted`` of ``proposed``
        draft tokens matched (``req.position`` is already advanced)."""

    def propose(self, req, k):
        """Up to ``k`` drafted continuation tokens (int32 1-D array) for
        one request; empty means "don't speculate this boundary"."""
        return _EMPTY

    def propose_batch(self, reqs, ks):
        """Drafts for every active row (``ks[i] == 0`` rows must get an
        empty draft).  Default: per-row :meth:`propose`."""
        return [self.propose(req, k) if k > 0 else _EMPTY
                for req, k in zip(reqs, ks)]


class NgramDrafter(Drafter):
    """Prompt-lookup self-drafting: propose the continuation of the most
    recent earlier occurrence of the context's own trailing n-gram.

    Tries suffix lengths ``max_ngram .. min_ngram`` (longest match wins;
    among equal lengths the most recent occurrence with a FULL ``k``
    -token continuation wins, else the one with the longest continuation
    — an occurrence hugging the end of the context predicts almost
    nothing) and returns up to ``k`` following tokens.  Deterministic
    pure function of the committed context — identical solo vs
    continuous by construction."""

    name = "ngram"

    def __init__(self, max_ngram=3, min_ngram=1, window=128):
        if int(min_ngram) < 1 or int(max_ngram) < int(min_ngram):
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"{min_ngram}/{max_ngram}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        self.window = int(window)       # lookback cap: drafting is on
        #                                 every step boundary's hot path

    def propose(self, req, k):
        ctx = _context(req)
        if ctx.size > self.window:
            ctx = ctx[ctx.size - self.window:]
        n_hi = min(self.max_ngram, ctx.size - 1)
        for n in range(n_hi, self.min_ngram - 1, -1):
            suffix = ctx[ctx.size - n:]
            windows = np.lib.stride_tricks.sliding_window_view(
                ctx[:ctx.size - 1], n)
            hits = np.nonzero((windows == suffix).all(axis=1))[0]
            if hits.size:
                starts = hits[::-1] + n          # most recent first
                avail = ctx.size - starts
                full = starts[avail >= int(k)]
                start = int(full[0] if full.size
                            else starts[int(np.argmax(avail))])
                cont = ctx[start:start + int(k)]
                if cont.size:
                    return np.asarray(cont, "int32")
        return _EMPTY


def resolve_drafter(spec):
    """``None`` / a :class:`Drafter` / the string ``"ngram"``."""
    if spec is None or isinstance(spec, Drafter):
        return spec
    if spec == "ngram":
        return NgramDrafter()
    raise ValueError(f"unknown drafter {spec!r} (want 'ngram' or a Drafter)")
