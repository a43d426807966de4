"""Production mesh builders.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e-256-like).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips across DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for debug runs (e.g. (2, 4) on 8 fake devices).

    Axes are ``Auto``: the model code places arrays with
    ``with_sharding_constraint`` and lets GSPMD propagate, which
    ``Explicit`` axes (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_axis(mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def data_axes(mesh):
    """The data-parallel axes present in this mesh ('pod' + 'data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
