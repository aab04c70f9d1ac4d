"""``make_mesh``: ``jax.make_mesh`` with Auto axis types.

``jax.make_mesh`` gives Explicit axis types by default; the meshes in this
repo rely on Auto sharding propagation (``jax.jit`` shardings and
``jax.shard_map``), so they are all built here.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)
