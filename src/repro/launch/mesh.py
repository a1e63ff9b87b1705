"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax init while tests/benches see 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis (pure DP + ZeRO over pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2) -> Mesh:
    """Small mesh for subprocess tests (requires >= n_data*n_model devices)."""
    return _mk((n_data, n_model), ("data", "model"))


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU smoke paths."""
    return _mk((1, 1), ("data", "model"))
