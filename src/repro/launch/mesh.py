"""Production mesh construction (a FUNCTION — importing this module never
touches jax device state; jax locks the device count on first init)."""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: sharding is propagated by the compiler, as the model code's
    # with_sharding_constraint hints expect (make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_devices: Optional[int] = None, *, multi_pod: bool = False):
    """Small mesh over however many (host) devices exist — used by tests."""
    n = n_devices or len(jax.devices())
    if multi_pod and n % 2 == 0:
        return _mesh((2, 1, n // 2), ("pod", "data", "model"))
    if n >= 4:
        return _mesh((2, n // 2), ("data", "model"))
    return _mesh((1, n), ("data", "model"))
