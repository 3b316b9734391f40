"""Seeded random projections of a tree of arrays.

A gap between two norms sees only the bias an error leaves (second order
in the error); the inner product with a fixed random direction sees the
error itself.  ``K`` directions give ``K`` scalars, which is all that
crosses from the reference to the comparison."""
import functools

N_PROJECTIONS = 16


def projection_key(seed):
    """The key of a run's directions: the program's side and the
    reference's draw the same ones."""
    from .weights import seed_key
    return seed_key(seed, stream=3)


@functools.partial(__import__("jax").jit, static_argnames=("k",))
def project(tree, key, k):
    """``(k,)`` float32: for each of ``k`` standard-normal directions drawn
    from ``key`` (one stream per leaf, leaves in sorted-name order), the sum
    over leaves of <leaf, direction>."""
    import jax
    import jax.numpy as jnp
    total = jnp.zeros((k,), jnp.float32)
    for i, name in enumerate(sorted(tree)):
        leaf = tree[name].astype(jnp.float32)
        r = jax.random.normal(jax.random.fold_in(key, i),
                              (k,) + leaf.shape, jnp.float32)
        total = total + jnp.tensordot(
            r.reshape(k, -1), leaf.reshape(-1), axes=1)
    return total


def projection_gap(program, reference):
    """Root-mean-square gap of the projections over the reference's
    root-mean-square projection: an estimate of |error| / |reference|."""
    import numpy as np
    p, r = np.asarray(program, "float64"), np.asarray(reference, "float64")
    return float(np.sqrt(np.mean((p - r) ** 2)) / np.sqrt(np.mean(r ** 2)))
