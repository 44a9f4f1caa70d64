"""Production mesh factory.

Per-pod mesh is 16x16 = 256 chips (v5e pod), axes (data, model); the
multi-pod mesh prepends a pure-DP "pod" axis: (2, 16, 16) = 512 chips.
A FUNCTION, not a module constant — importing this module must never touch
jax device state (smoke tests see 1 CPU device; only dryrun.py forces 512).
Every axis is ``AxisType.Auto``; enter a mesh with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_mesh(shape, axes):
    """Arbitrary mesh for elastic restarts / tests (e.g. (2, 4) on 8 CPUs)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
