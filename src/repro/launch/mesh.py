"""Production mesh construction (multi-pod dry-run deliverable).

A FUNCTION, not a module constant: importing this module never touches jax
device state, so launchers can set XLA_FLAGS first.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "POD_SHAPE", "MULTI_POD_SHAPE"]

POD_SHAPE = (16, 16)                # 256 chips/pod: (data, model)
MULTI_POD_SHAPE = (2, 16, 16)       # 2 pods = 512 chips: (pod, data, model)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
