"""The jax.sharding surface the repo builds meshes and shard_maps with.

Thin wrappers over ``jax.make_mesh`` (explicitly Auto axis types),
``jax.shard_map`` (a named set of manual axes) and the ambient mesh
(``jax.sharding.set_mesh`` / ``get_abstract_mesh``), so every caller
states the same defaults once.
"""
from __future__ import annotations

from typing import Callable, Iterable, Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis Auto: the GSPMD default this
    codebase assumes everywhere outside a shard_map."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs)


def shard_map(f: Callable, mesh, in_specs, out_specs,
              manual_axes: Optional[Iterable[str]] = None,
              check: bool = False) -> Callable:
    """``jax.shard_map`` over ``manual_axes`` (None → every mesh axis); the
    remaining axes stay auto (GSPMD partitions the per-shard program as
    usual). ``check`` maps to ``check_vma``."""
    all_axes = frozenset(mesh.axis_names)
    manual = frozenset(manual_axes) if manual_axes is not None else all_axes
    unknown = manual - all_axes
    if unknown:
        raise ValueError(f"manual axes {sorted(unknown)} not in mesh axes "
                         f"{sorted(all_axes)}")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check)


def activate_mesh(mesh):
    """Install ``mesh`` as the ambient mesh for the rest of the process
    (launchers call this once after building the production mesh)."""
    jax.sharding.set_mesh(mesh)
    return mesh


def current_mesh():
    """The ambient mesh, or None. Works inside and outside jit tracing;
    inside a shard_map its manual axes carry the Manual axis type."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh
