"""Mesh construction. Functions only — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 256 chips/pod (16x16), optionally 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
