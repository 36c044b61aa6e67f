"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and tests/benches must keep seeing the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(devices: int | None = None, model: int = 2):
    """Small host mesh for tests (run under a subprocess that sets
    --xla_force_host_platform_device_count)."""
    n = devices or jax.device_count()
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
