"""Production mesh construction.

Never touches jax device state at import time — everything is behind
functions (the dry-run sets XLA_FLAGS before first jax init; tests keep their
single CPU device).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (v5e pod slice); 2 pods for the multi-pod run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_devices: int | None = None, axis: str = "data"):
    """Small mesh over whatever devices exist (tests, examples)."""
    devs = jax.devices()[:n_devices] if n_devices else jax.devices()
    return make_mesh((len(devs),), (axis,))
