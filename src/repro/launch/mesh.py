"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax init).

Meshes are built with Auto axes: `jax.make_mesh` defaults to Explicit
axes, under which sharding is part of every array's type and the
vocab-sharded embedding gather (`models.modules`) is a type error.  The
model code states its layout with sharding constraints under a mesh
context (`with mesh:`), which is what Auto axes are for.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """`jax.make_mesh` with every axis Auto (see the module docstring)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist —
    used by tests and the CPU examples."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, "
                         f"have {n}")
    return make_mesh((data, model), ("data", "model"))
