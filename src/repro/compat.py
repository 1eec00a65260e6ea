"""The mesh/collective API surface the repo uses, in one place.

Every call site goes through this module instead of touching the moving
JAX APIs directly, so a JAX upgrade is a one-file audit.  Written for
JAX 0.9: ``jax.shard_map(..., axis_names=..., check_vma=...)``,
``jax.set_mesh(mesh)`` and ``jax.sharding.get_abstract_mesh()``.
"""

from __future__ import annotations

from typing import Optional, Set

import jax


def get_abstract_mesh():
    """The mesh currently in scope (``.empty``, ``.axis_names``, ``.shape``,
    ``.axis_types``)."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(mesh):
    """Context manager scoping ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict; ``{}`` when the backend
    reports nothing."""
    return compiled.cost_analysis() or {}


def mesh_axis_types(mesh) -> dict:
    """``{axis_name: axis_type}`` of ``mesh``."""
    return dict(zip(mesh.axis_names, mesh.axis_types))


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Set[str]] = None, check_vma: bool = False):
    """``jax.shard_map`` with the replication checker off by default.

    ``axis_names`` is the set of mesh axes the body is *manual* over (the
    rest stay auto/GSPMD); ``None`` means manual over every mesh axis.
    """
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)
