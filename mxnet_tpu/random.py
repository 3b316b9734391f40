"""Global random state bridging MXNet's seeded-RNG API to JAX keys.

Reference: ``python/mxnet/random.py`` (``mx.random.seed``) backed by
per-device ``RandomGenerator`` resources (``include/mxnet/random_generator.h``)
handed to ops via ``ResourceRequest::kRandom`` (``include/mxnet/resource.h:42``).

TPU-native redesign: a process-global ``jax.random`` key, split once per
stochastic op invocation.  Determinism follows from the seed alone (keys are
counter-based), which is *stronger* than the reference's per-thread generators
— re-running a seeded program yields bitwise-identical streams regardless of
engine scheduling, subsuming ``MXNET_ENFORCE_DETERMINISM``.
"""
from __future__ import annotations

import threading

import jax

_lock = threading.Lock()
# the root key is made from the seed on first use, never at import or in
# seed(): a process that only imports the framework (a gateway front end,
# a supervisor) must not initialise a backend — on a chip machine that
# takes the chip away from the child that needs it
_seed = [0]
_key = [None]
# host-side stream for initializers (reference initializers run on mxnet's
# seeded RNG ops, so mx.random.seed must determinize them here too)
import numpy as _np
np_rng = _np.random.RandomState(0)
# pre-split pool: one eager split per POOL draws instead of one per draw —
# an eager jax.random.split costs ~1.5 ms of dispatch, which would otherwise
# dominate every stochastic op and every CachedOp call
_POOL = 128
_pool = {"keys": None, "i": 0, "last": None}


def seed(seed_state, ctx="all"):
    """Reset the global key (reference ``mx.random.seed``)."""
    with _lock:
        _seed[0] = int(seed_state)
        _key[0] = None
        _pool["keys"] = None
        _pool["i"] = 0
        _pool["last"] = None
        np_rng.seed(int(seed_state))


def _root_key():
    """The global key (call under ``_lock``)."""
    if _key[0] is None:
        _key[0] = jax.random.PRNGKey(_seed[0])
    return _key[0]


_tls = threading.local()


class key_scope:
    """Thread-local override of the key stream: inside the scope, ``next_key``
    splits from the given (possibly traced) key instead of the process-global
    one.  This is how jit-traced composite calls (CachedOp — the analog of
    Gluon ``hybridize()``) thread randomness: the key is a *dynamic argument*
    of the compiled function, so replays draw fresh masks while staying
    deterministic under ``mx.random.seed``."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        # frame-local "last" so current_key() inside a traced scope sees the
        # traced stream — and the tracer can never leak past __exit__
        stack.append({"key": self._key, "last": None})
        return self

    def __exit__(self, *a):
        _tls.stack.pop()


def next_key():
    """Split one subkey off the active stream (called by the op frontend for
    each stochastic op invocation)."""
    stack = getattr(_tls, "stack", None)
    if stack:
        # traced scope: splits are recorded into the trace, not dispatched
        frame = stack[-1]
        frame["key"], sub = jax.random.split(frame["key"])
        frame["last"] = sub
        return sub
    with _lock:
        if _pool["keys"] is None or _pool["i"] >= _POOL:
            ks = jax.random.split(_root_key(), _POOL + 1)
            _key[0] = ks[0]
            # host copy: a numpy row IS a valid key and slices for free —
            # a device-array __getitem__ costs a full eager dispatch
            _pool["keys"] = _np.asarray(ks[1:])
            _pool["i"] = 0
        sub = _pool["keys"][_pool["i"]]
        _pool["i"] += 1
        _pool["last"] = sub
        return sub


def get_state():
    """Snapshot the full key-stream state (global key + pre-split pool) as
    host numpy arrays — picklable, and byte-exact.

    Restoring this snapshot with :func:`set_state` makes the subsequent
    ``next_key()`` sequence bitwise-identical to what the snapshotted
    process would have drawn: this is how ``ResilientTrainer`` checkpoints
    randomness so a crash/resume boundary does not fork the RNG stream.
    Does NOT capture the numpy initializer stream (``np_rng``) — parameter
    init happens before training, which is what checkpoints bracket."""
    with _lock:
        return {
            "key": _np.asarray(_root_key()).copy(),
            "pool_keys": None if _pool["keys"] is None
            else _pool["keys"].copy(),
            "pool_i": _pool["i"],
            "pool_last": None if _pool["last"] is None
            else _np.asarray(_pool["last"]).copy(),
        }


def set_state(state):
    """Restore a :func:`get_state` snapshot (exact stream continuation)."""
    with _lock:
        _key[0] = jax.numpy.asarray(state["key"])
        _pool["keys"] = None if state["pool_keys"] is None \
            else _np.asarray(state["pool_keys"]).copy()
        _pool["i"] = int(state["pool_i"])
        _pool["last"] = None if state.get("pool_last") is None \
            else _np.asarray(state["pool_last"])


def current_key():
    """The most recently issued key — consumers that *re-run* the last
    stochastic computation must see the same stream the forward drew, and
    it must differ draw to draw (the pool no longer advances ``_key[0]``
    per draw).  Inside a traced ``key_scope`` the scope's own last split is
    returned (a tracer — valid only within that trace); eager state is
    read under the pool lock.  NOTE: the executor captures its forward key
    explicitly (``executor.py``) rather than re-querying here, so an eager
    stochastic op between its forward and backward cannot desync the
    fwd/bwd pairing."""
    stack = getattr(_tls, "stack", None)
    if stack:
        frame = stack[-1]
        return frame["last"] if frame["last"] is not None else frame["key"]
    with _lock:
        if _pool["last"] is not None:
            return _pool["last"]
        return _root_key()


# The user-facing sampling functions (mx.random.uniform etc.) are installed by
# ndarray/register.py from the op table; this module also re-exports them.
