"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh() -> jax.sharding.Mesh:
    """Single-device mesh for CPU smoke/bench runs."""
    dev = jax.devices()[:1]
    return jax.sharding.Mesh(np.asarray(dev).reshape(1, 1), ("data", "model"))
