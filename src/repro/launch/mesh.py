"""Production mesh definition.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device counts are locked on first jax init,
and only the dry-run is allowed to fake 512 host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices actually exist (tests, CPU driver)."""
    n = len(jax.devices())
    data = data or (n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
