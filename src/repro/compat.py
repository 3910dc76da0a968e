"""Mesh helpers for the sharding-aware layers.

Meshes in this repo are GSPMD-controlled: every axis is ``AxisType.Auto``
(``jax.make_mesh`` defaults to Explicit).  Inside a partial-manual
``jax.shard_map`` the manual axes must not appear in sharding constraints,
so the models, trainer and serving engine ask :func:`auto_axis_names` which
axes they may still pin.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-controlled)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def get_abstract_mesh():
    """The current abstract mesh, or None outside any mesh context."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def auto_axis_names(mesh) -> set:
    """Names of mesh axes still under GSPMD (Auto) control."""
    if mesh is None:
        return set()
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types) if t == AxisType.Auto}
