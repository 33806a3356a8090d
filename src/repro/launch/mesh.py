"""Device meshes. Functions, not module constants: importing this module
touches no jax device state and sets no environment variable, so a
process that forces a host device count (``XLA_FLAGS``) may still do so
after importing it."""
from __future__ import annotations

import jax


def _mk(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 1):
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod > 1:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))
