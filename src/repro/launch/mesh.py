"""Production meshes.

A function, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``. ``make_mesh`` makes
    Explicit axes by default, and the step builders' activation
    ``with_sharding_constraint`` may only refer to Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return auto_mesh((n // model, model), ("data", "model"))
