"""Production mesh builders (functions — importing never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before first init)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=axis_types)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    axis_types = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=axis_types)
