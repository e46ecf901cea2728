"""Mesh construction.

``jax.make_mesh`` makes every axis Explicit by default; the repo's
sharded programs rely on GSPMD propagation (Auto axes) outside their
``jax.shard_map`` regions. ``auto_mesh`` is the one place a mesh is
built.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """Mesh with every axis in 'auto' sharding mode (the repo default)."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names))
