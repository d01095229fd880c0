"""Production mesh factories.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax
call; smoke tests must keep seeing 1 device).

Topology (TPU v5e pods):
  single-pod  (16, 16)    → ("data", "model")      256 chips, all-ICI
  multi-pod   (2, 16, 16) → ("pod", "data", "model")  512 chips; the
              leading ``pod`` axis is the DCN hop (pure DP + optionally
              compressed gradient reduction — DESIGN.md §6).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh over however many (fake) devices a test process has."""
    return _auto_mesh(shape, axes)
