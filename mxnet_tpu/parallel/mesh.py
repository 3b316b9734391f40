"""Device mesh construction.

The mesh is the TPU-native analog of the reference's device topology handling:
``src/kvstore/gpu_topology.h`` discovers a GPU link matrix and builds
reduction trees; on TPU the torus topology is known to XLA, so the framework
only needs to *name* the axes and let the compiler route collectives.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

_current = []


def device_mesh(axes, devices=None):
    """Build a ``jax.sharding.Mesh`` from ``{axis_name: size}``.

    Use ``-1`` for at most one axis to absorb the remaining devices
    (np.reshape semantics).  Axis order is ICI-locality order: the *last* axis
    has nearest-neighbor devices, so put the most bandwidth-hungry axis
    (usually ``tp``) last.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = list(axes.keys())
    sizes = list(axes.values())
    n = len(devices)
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        sizes[sizes.index(-1)] = n // known
    if math.prod(sizes) != n:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} does not cover {n} devices")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def make_mesh(n_devices=None, dp=None, tp=1, sp=1, pp=1):
    """Convenience 1-4 axis mesh: ``(pp, dp, sp, tp)`` with dp absorbing the
    remainder. Singleton axes are kept so one sharding code path serves every
    configuration."""
    import jax
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if dp is None:
        dp = len(devices) // (tp * sp * pp)
    return device_mesh({"pp": pp, "dp": dp, "sp": sp, "tp": tp},
                       devices=devices)


def current_mesh():
    """Innermost mesh entered via :func:`use_mesh`, else the one set with
    ``jax.set_mesh``; ``None`` outside both."""
    if _current:
        return _current[-1]
    import jax
    mesh = jax.sharding.get_mesh()
    return None if mesh.empty else mesh


@contextlib.contextmanager
def use_mesh(mesh):
    _current.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _current.pop()
