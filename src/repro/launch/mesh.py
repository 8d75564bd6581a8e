"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], **kwargs):
    """`jax.make_mesh` with Auto axes: the sharding rules place arrays with
    `with_sharding_constraint` and let the partitioner propagate the rest,
    which Explicit axes (jax.make_mesh's default) refuse."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        **kwargs,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh over however many (possibly fake) devices tests have."""
    return make_mesh((data, model), ("data", "model"))
