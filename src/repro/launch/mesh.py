"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  Production target: TPU v5e, 256 chips/pod as a
16x16 (data, model) mesh; the multi-pod config adds a leading "pod" axis
(2 pods = 512 chips).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the model stack shards
    through ``with_sharding_constraint`` and ``shard_map``, which the
    ``Explicit`` default rejects."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh for subprocess-based multi-device tests."""
    return _auto_mesh((data, model), ("data", "model"))


def mesh_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
