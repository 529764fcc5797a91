"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds the 2-pod 'pod' axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic helper: whatever topology the (restarted) job got."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def host_mesh(n: int = 0, model: int = 1):
    """Small debug mesh over host platform devices."""
    n = n or len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
