"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import)."""

from __future__ import annotations

from typing import Tuple

import jax

from ..placement.sharding_rules import MeshShape


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def mesh_shape(*, multi_pod: bool = False) -> MeshShape:
    """Planner-side description matching make_production_mesh."""
    if multi_pod:
        return MeshShape({"pod": 2, "data": 16, "model": 16})
    return MeshShape({"data": 16, "model": 16})


def make_smoke_mesh(devices=None):
    """Tiny mesh over however many devices exist (tests)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = 2 if n % 2 == 0 and n > 1 else 1
    data = n // model
    # Auto axes: the planner's rules are constraints the compiler propagates
    # (jax.make_mesh defaults to Explicit axes, which type every op's sharding).
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh((data, model), ("data", "model"), axis_types=auto)
