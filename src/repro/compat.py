"""Mesh and shard_map entry points shared by every call site.

Meshes are built with explicitly-Auto axis types, and shard_map runs with
its replication checks off (the serve steps psum their outputs).  Keeping
both in one place keeps those two policies consistent across the repo.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """jax.make_mesh with explicitly-Auto axis types."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def shard_map(f, *, mesh, in_specs, out_specs):
    """shard_map with per-output replication checks off (psum'd outputs)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
