"""Production mesh construction (spec-mandated shapes).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked at first jax init, and the
dry-run must set XLA_FLAGS before that happens).
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests, local runs)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
