"""Shared spellings of the mesh / shard_map API (jax >= 0.9).

Every internal module builds meshes and shard_maps through here, so the
axis types and the ``check_vma`` default live in one place.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape, axes):
    """jax.make_mesh with explicit-Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def axis_size(axis_name) -> int:
    """Size of a named mesh axis (or product over a tuple of axes) inside
    shard_map."""
    return jax.lax.axis_size(axis_name)


def get_abstract_mesh():
    """Mesh of the current tracing context."""
    return jax.sharding.get_abstract_mesh()
