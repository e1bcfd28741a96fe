"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must keep seeing the single real device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def _auto(n: int):
    """``Auto`` axis types: the steps shard through GSPMD sharding
    constraints, which ``jax.make_mesh``'s ``Explicit`` default refuses."""
    return (jax.sharding.AxisType.Auto,) * n


def make_test_mesh(n_devices: int = 8):
    """Small host-device mesh for CPU integration tests (data x model)."""
    d = min(n_devices, len(jax.devices()))
    assert d % 2 == 0, d
    return jax.make_mesh((d // 2, 2), ("data", "model"),
                         axis_types=_auto(2))
