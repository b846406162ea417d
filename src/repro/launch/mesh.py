"""Production meshes (DESIGN.md §5).

A function, not a module constant, so importing never touches jax device
state.  Single pod: 16x16 = 256 chips ("data", "model").  Multi-pod:
2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis carries the
inter-pod (Ethernet/DCN) data parallelism that STrack accelerates.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Mesh over the visible devices with Auto axis types (e.g. (1,1) smoke
    meshes for tests/examples)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
