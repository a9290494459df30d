"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run sets the 512-placeholder-device flag
before any jax initialisation)."""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per TPU v5e pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (real or forced) local devices exist."""
    return _mk((data, model), ("data", "model"))
