"""One place for the mesh / sharding calls the codebase shares.

Written for the installed jax (>= 0.9): meshes carry explicit ``Auto``
axis types, the ambient mesh is ``jax.set_mesh``, and
``Compiled.cost_analysis()`` is a dict. Call sites route through here so
the next API change is repaired in one file.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Sequence

import jax


def auto_axis_types(n: int):
    """``(AxisType.Auto,) * n``."""
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(shape: Sequence[int], names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(names), axis_types=auto_axis_types(len(names))
    )


def abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """Device-free mesh for shape-only sharding computations."""
    return jax.sharding.AbstractMesh(
        tuple(shape), tuple(names), axis_types=auto_axis_types(len(names))
    )


def set_mesh(mesh) -> contextlib.AbstractContextManager:
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def get_abstract_mesh():
    """The mesh currently in scope (an empty mesh when none is)."""
    return jax.sharding.get_abstract_mesh()


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with a keyword mesh."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def cost_analysis(compiled) -> Dict[str, Any]:
    """``Compiled.cost_analysis()`` as a flat dict."""
    return compiled.cost_analysis() or {}
