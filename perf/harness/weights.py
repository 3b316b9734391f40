"""Seeded weights, made on the device in one jitted call.

The benchmark owns the weights: the program is handed these arrays the way a
deployment hands it a checkpoint, and the plain reference makes the same
arrays again from the same seed (it takes nothing from the program).  The
table of tensors is the family's plain reference's (``perf/reference/<f>.py``
``shapes(cfg)``); names are the benchmark's own, and ``perf/systems`` maps
them onto the program's.
"""
import functools


def seed_key(seed, stream=0):
    """A PRNG key for any whole-number ``seed`` (the driver's are past
    2**31): the low and high halves are folded in, then the stream."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7fffffff)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7fffffff)
    return jax.random.fold_in(key, int(stream))


def host_rng(seed, stream=0):
    """numpy Generator for host-side draws (token ids, order)."""
    import numpy as np
    return np.random.default_rng([int(seed), int(stream)])


def _normal_tree(key, shapes, std, dtype):
    import jax
    import jax.numpy as jnp
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        if kind == "normal":
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32) * std).astype(dtype)
        elif kind == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = jnp.zeros(shape, dtype)
    return out


def make_weights(shapes, std, seed, device=None):
    """``{name: array}`` for ``shapes`` (``{name: (shape, "normal" | "ones"
    | "zeros")}``, the table a plain reference gives for its family) from
    ``seed``: one jitted program, run on ``device`` (default: JAX's first),
    float32, normals of standard deviation ``std``."""
    import jax
    import jax.numpy as jnp
    make = jax.jit(functools.partial(_normal_tree, shapes=shapes,
                                     std=float(std), dtype=jnp.float32))
    key = seed_key(seed, stream=1)
    if device is not None:
        key = jax.device_put(key, device)
    return make(key)
