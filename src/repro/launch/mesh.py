"""Production mesh construction (multi-pod dry-run step 1).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    """Every axis in Auto mode: the compiler propagates shardings from
    the ``in_shardings`` / ``with_sharding_constraint`` annotations."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary test meshes (e.g. (2,2) on 4 fake devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    return sizes.get("model", 1)
