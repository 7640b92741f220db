"""Production mesh builders (brief §MULTI-POD DRY-RUN).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small host-device mesh for subprocess tests (8 CPU devices)."""
    return _mesh(shape, axes)
