"""Production meshes. A FUNCTION (not a module-level constant) so importing
this module never touches jax device state. Axes are Auto: the model code
places arrays with sharding constraints and lets GSPMD propagate them."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n: int = None, axes=("data",)):
    """Small CPU mesh for tests/examples (n real host devices)."""
    devs = jax.devices()
    n = n or len(devs)
    shape = (n,) if len(axes) == 1 else None
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:n])
