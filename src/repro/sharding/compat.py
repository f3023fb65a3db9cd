"""Mesh constructors for the installed jax (see `pyproject.toml`).

Every mesh in this repo is built through these helpers so that all of
its axes are Auto-typed: `jax.make_mesh` defaults to Explicit axes, and
the engine's sharding constraints and `jax.shard_map` blocks are written
for Auto ones (DESIGN.md §6).
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """Auto-typed AbstractMesh from parallel (sizes, names) tuples, e.g.
    ``abstract_mesh((16, 16), ("data", "model"))``."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


def make_mesh(axis_sizes, axis_names, **kw):
    """`jax.make_mesh` with all axes Auto-typed."""
    kw.setdefault("axis_types", (AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(axis_sizes), tuple(axis_names), **kw)


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size} of a Mesh or AbstractMesh."""
    return dict(mesh.shape)
