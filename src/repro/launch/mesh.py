"""Device meshes: the production pods and the meshes the launchers build.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — dryrun.py must pin XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    return make_mesh((2, 16, 16) if multi_pod else (16, 16))


def make_mesh(dims: tuple[int, ...]):
    """A mesh over ``prod(dims)`` devices.  One axis is data parallel
    (``data``), two are ``(data, model)``, three ``(pod, data, model)``."""
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}.get(len(dims))
    if axes is None:
        raise ValueError(f"a mesh has 1 to 3 axes, got {dims}")
    return jax.make_mesh(tuple(dims), axes,
                         axis_types=(AxisType.Auto,) * len(dims))

