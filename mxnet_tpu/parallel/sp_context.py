"""Sequence-parallel scope: lets model code (attention layers) discover the
active ``sp`` mesh so long-context models run sharded *inside* the fused
SPMD train step (SURVEY.md §5.7 — "exposed as a ``sequence`` mesh axis in
the same sharding API as DP/TP").

Usage: ``SPMDTrainer(..., sequence_parallel=True)`` with a mesh whose
``sp`` axis size > 1 activates the scope around tracing; an attention layer
calls :func:`current_sequence_parallel` and, when set, routes through
:func:`ring_self_attention` instead of local attention.
"""
from __future__ import annotations

import contextlib

__all__ = ["sequence_parallel_scope", "current_sequence_parallel",
           "traced_mesh_scope", "traced_mesh"]

_SCOPE = []
_MESH = []


@contextlib.contextmanager
def sequence_parallel_scope(mesh, sp_axis="sp", dp_axis="dp", impl="ring"):
    """``impl``: "ring" (K/V rotate over ICI, any head count) or "ulysses"
    (all_to_all head sharding — needs heads divisible by the sp size)."""
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    _SCOPE.append((mesh, sp_axis, dp_axis, impl))
    try:
        yield
    finally:
        _SCOPE.pop()


def current_sequence_parallel():
    """(mesh, sp_axis, dp_axis, impl) when inside a scope with sp size > 1."""
    if not _SCOPE:
        return None
    mesh, sp_axis, dp_axis, impl = _SCOPE[-1]
    if mesh.shape.get(sp_axis, 1) <= 1:
        return None
    return mesh, sp_axis, dp_axis, impl


@contextlib.contextmanager
def traced_mesh_scope(mesh, dp_axis="dp", tp_axis="tp"):
    """The mesh a step is being traced for.  XLA partitions its own
    operations over it; a Mosaic kernel it cannot, so a layer that calls one
    reads the mesh here and maps the kernel over the shards itself
    (``ops/__init__.py`` does for the attention kernels)."""
    _MESH.append((mesh, dp_axis, tp_axis))
    try:
        yield
    finally:
        _MESH.pop()


def traced_mesh():
    """(mesh, dp_axis, tp_axis) when inside a scope whose mesh has more
    than one device."""
    if not _MESH or _MESH[-1][0].size <= 1:
        return None
    return _MESH[-1]
