"""The mesh wrappers every module uses, so the rest of the codebase has one
spelling for replication-unchecked ``shard_map``, Auto-typed meshes and
static axis sizes.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = ["shard_map", "make_mesh", "axis_size"]


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (our collectives
    intentionally produce replicated outputs from sharded inputs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> Any:
    """``jax.make_mesh`` with explicit-collective (Auto) axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))


def axis_size(name: str) -> int:
    """Static size of a named mesh axis, callable inside a shard_map body."""
    return jax.lax.axis_size(name)
