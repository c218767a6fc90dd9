"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run pins the device count via XLA_FLAGS
before any jax import; tests and benches must keep seeing 1 device).
Meshes use ``AxisType.Auto`` axes; enter one with ``jax.set_mesh(mesh)``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """TPU v5e: 256 chips/pod as (data=16, model=16); two pods add a
    leading "pod" (pure-DP) axis crossing the inter-pod DCI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests use small host-device meshes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
