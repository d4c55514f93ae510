"""Shared spellings of the installed JAX's APIs (jax 0.9).

Every shard_map call, mesh-axis lookup and compiled-cost read goes
through here, so a later JAX that renames one of them changes one file.
The ``compat-shim`` lint rule keeps version probes out of every other
module.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def mesh_axis_size(mesh, axis: str, default: int = 1) -> int:
    """Size of a named mesh axis, ``default`` if absent (or ``mesh`` is None)."""
    if mesh is None:
        return default
    return int(mesh.shape.get(axis, default))


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}
