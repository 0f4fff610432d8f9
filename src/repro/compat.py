"""One place for the mesh / sharding calls the codebase shares.

Written for the installed jax (>= 0.9): meshes carry explicit ``Auto``
axis types and ``shard_map`` is ``jax.shard_map``. Call sites route
through here so the next API change is repaired in one file.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with a keyword mesh."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
