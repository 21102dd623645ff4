"""The mesh / shard_map spellings the repo uses, in one place.

The installed JAX has the explicit-mesh surface (``jax.make_mesh(...,
axis_types=...)``, ``jax.set_mesh``, ``jax.sharding.get_abstract_mesh``,
``jax.shard_map``); these helpers pin the choices every caller shares:

  * :func:`make_mesh`     — Auto axis types on every axis.
  * :func:`use_mesh`      — context manager activating a mesh.
  * :func:`current_mesh`  — the ambient abstract mesh (``.empty`` when none).
  * :func:`shard_map`     — ``jax.shard_map``.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def use_mesh(mesh: Mesh):
    """Context manager activating ``mesh`` for sharding constraints."""
    return jax.set_mesh(mesh)


def current_mesh():
    """The ambient abstract mesh; ``.empty`` when none is active."""
    return jax.sharding.get_abstract_mesh()


def axis_size(axis) -> int:
    """Size of a mesh axis inside a shard_map body."""
    return jax.lax.axis_size(axis)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` — a flat dict of the program's costs."""
    return compiled.cost_analysis()


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
