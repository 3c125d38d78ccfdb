"""Mesh / sharding API: mesh construction, the ambient (abstract) mesh
that ``with_sharding_constraint(PartitionSpec)`` reads at trace time,
and ``shard_map``."""
from __future__ import annotations

import contextlib

import jax


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


@contextlib.contextmanager
def set_mesh(mesh):
    """Install `mesh` as the ambient (abstract) mesh for tracing."""
    with jax.set_mesh(mesh):
        yield mesh


def active_mesh():
    """The abstract mesh in scope at trace time; None when unmeshed."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def active_mesh_axis_names() -> tuple:
    """Axis names of the mesh in scope at trace time; () when unmeshed."""
    m = active_mesh()
    return tuple(m.axis_names) if m is not None else ()


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size} for physical or abstract meshes."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with keyword mesh/specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)
