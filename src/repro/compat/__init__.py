"""JAX API layer.

The repo targets the installed JAX (0.9.0, jaxlib 0.9.0, libtpu 0.0.34).
The APIs below are the ones whose spelling has moved between JAX
releases; source modules import the stable names here and never touch
the drifting spellings directly, so the next move is absorbed in one
place (the ``jax-drift`` lint in :mod:`repro.analysis.lints` enforces
this).

Stable surface:
  * :func:`tpu_compiler_params`      — ``pltpu.CompilerParams``
  * :func:`make_mesh`                — ``jax.make_mesh`` with Auto axes
  * :func:`set_mesh`                 — ``jax.set_mesh``
  * :func:`active_mesh`              — ``jax.sharding.get_abstract_mesh``
  * :func:`active_mesh_axis_names`   — its axis names
  * :func:`mesh_axis_sizes`          — ``Mesh.axis_sizes`` by name
  * :func:`shard_map`                — ``jax.shard_map``
  * :func:`normalize_cost_analysis`  — ``cost_analysis()`` -> flat dict
  * :func:`xla_cost_analysis`        — Compiled -> flat dict
  * :func:`tree_map`                 — ``jax.tree.map``
"""
from __future__ import annotations

from .hlo import normalize_cost_analysis, xla_cost_analysis
from .pallas import tpu_compiler_params
from .sharding import (active_mesh, active_mesh_axis_names, make_mesh,
                       mesh_axis_sizes, set_mesh, shard_map)
from .tree import tree_map

__all__ = [
    "tpu_compiler_params",
    "make_mesh",
    "set_mesh",
    "active_mesh",
    "active_mesh_axis_names",
    "mesh_axis_sizes",
    "shard_map",
    "normalize_cost_analysis",
    "xla_cost_analysis",
    "tree_map",
]
