"""The mesh and sharding calls this repo makes, in one place.

Written against the installed jax (0.9): meshes carry explicit
``AxisType.Auto`` axes, the ambient mesh is ``jax.sharding.set_mesh``, and
``jax.shard_map`` is the manual-partitioning entry point.

* :func:`make_compat_mesh` — ``jax.make_mesh`` with Auto axes.
* :data:`use_mesh` — enter a mesh as the ambient mesh for sharding
  constraints.
* :data:`shard_map` — ``jax.shard_map``.
* :func:`ambient_mesh` — the mesh model code should resolve logical axis
  names against, or None (-> sharding constraints no-op, keeping model code
  mesh-agnostic).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

use_mesh = jax.sharding.set_mesh
shard_map = jax.shard_map


def make_compat_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def ambient_mesh():
    """The mesh logical-axis constraints should resolve against, or None.

    None also when the ambient mesh has explicit (non-Auto) axis types —
    with_sharding_constraint only accepts Auto axes, so callers must no-op
    inside shard_map manual regions.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or any(t != AxisType.Auto for t in mesh.axis_types):
        return None
    return mesh
