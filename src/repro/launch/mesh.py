"""Production mesh definitions (functions — importing this module never
touches jax device state)."""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh_for", "make_tp_mesh"]


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 1, axes=("data", "model")):
    """Small helper for tests/examples on arbitrary device counts."""
    assert devices % model_parallel == 0
    return _auto_mesh((devices // model_parallel, model_parallel), axes)


def make_tp_mesh(tp: int):
    """One-axis ``("tp",)`` mesh for tensor-parallel sharded serving
    (serving/sharded.py).  The axis name is deliberately NOT "model":
    the logical-axis sharding rules and ``constrain()`` only react to
    pod/data/model, so the existing mesh machinery stays inert and the
    serving step's sharding is governed solely by its shard_map specs."""
    return _auto_mesh((tp,), ("tp",))
