"""Production meshes (DESIGN.md §5).

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int | None = None, model: int | None = None):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    if data is None or model is None:
        model = 1
        while model * 2 <= min(4, n) and n % (model * 2) == 0:
            model *= 2
        data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
