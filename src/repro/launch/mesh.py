"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding rules place
    arrays with ``with_sharding_constraint``, which only ``Auto`` axes
    accept (``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(devices: int = 8):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return auto_mesh((devices // 2, 2), ("data", "model"))
