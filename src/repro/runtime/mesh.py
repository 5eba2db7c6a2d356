"""Device-mesh construction and axis conventions.

Axis semantics (cf. docs/ARCHITECTURE.md §Mesh and collectives):
  pod    — data-parallel replica groups across pods (slowest links / DCN)
  data   — FSDP + batch partitioning within a pod
  model  — tensor/expert parallelism (fastest collectives)

Nothing in this module touches jax device state at import time; meshes are
built by functions so that ``XLA_FLAGS=--xla_force_host_platform_device_count``
set by a launcher before first jax use is respected.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh", "make_host_mesh", "batch_axes", "mesh_axis_size",
    "current_mesh", "use_mesh", "MESH_AXES",
]

MESH_AXES = ("pod", "data", "model")

_ACTIVE_MESH: list = []


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` pinned to Auto axis types (we steer sharding with
    constraints, the GSPMD analogue of Algebricks' partitioning properties)."""
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} / axes {axes} rank mismatch")
    need = int(np.prod(shape))
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"mesh {tuple(shape)} needs {need} devices but only {have} are "
            f"visible")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh for CPU tests; collapses to whatever devices exist."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return make_mesh((data, model), ("data", "model"))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes over which the batch (and gradients) are partitioned."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1] if _ACTIVE_MESH else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate a mesh both for our constraint helpers and as the jax mesh
    context (so ``with_sharding_constraint`` resolves named axes)."""
    _ACTIVE_MESH.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESH.pop()
