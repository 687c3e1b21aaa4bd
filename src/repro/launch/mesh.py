"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this
module never touches jax device state. Single pod = 16x16 = 256 chips
(TPU v5e pod slice); multi-pod = 2 x 16 x 16 = 512 chips with a leading
'pod' axis (pure DP across the slow inter-pod link).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    return _auto_mesh((1, n, 1), ("pod", "data", "model"))
