"""Production meshes. Functions, not module constants: importing this
module never touches jax device state (the dry-run sets the fake device
count before any jax initialization)."""
from __future__ import annotations

import jax


def _mk(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model) -- `pod` is
    pure cross-pod data parallelism over DCN/ICI-superpod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 1):
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod > 1:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def mesh_axes_of(mesh):
    from ..parallel import axes as A
    return A.MeshAxes.from_mesh(mesh)
