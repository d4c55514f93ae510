"""Functional parameter-schema system.

Single source of truth: a model declares a *schema* — a pytree of
``ParamInfo`` — from which we derive (a) initialized parameters,
(b) PartitionSpecs for pjit, and (c) abstract ShapeDtypeStructs for
dry-run lowering. This guarantees params and shardings never drift.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ParamInfo:
    shape: tuple
    dtype: Any = jnp.float32
    spec: P = P()
    # 'normal:<scale>' | 'zeros' | 'ones' | 'embed:<scale>' | 'ssm_a' | 'dt_bias'
    init: str = "normal:0.02"

    def initialize(self, key: jax.Array) -> jax.Array:
        dtype = jnp.dtype(self.dtype)
        # an f32 leaf is its own draw; a narrower one is cast inside one
        # jitted program, so the f32 draw fuses into the cast and never
        # exists as a whole-leaf array (a full-vocabulary ramp-head stack
        # is larger in f32 than a 16 GB chip)
        draw = _draw if dtype == jnp.float32 else _draw_fused
        return draw(key, tuple(self.shape), dtype, self.init)


def _draw(key, shape, dtype, init):
    """One leaf's initial value, drawn in f32 and cast to ``dtype``."""
    kind, _, arg = init.partition(":")
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind in ("normal", "embed"):
        scale = float(arg) if arg else 0.02
        # fan-in scaled init for 2D+ weights
        x = jax.random.normal(key, shape, jnp.float32) * scale
        return x.astype(dtype)
    if kind == "ssm_a":  # A_log init in [log(1), log(16)) per Mamba2
        lo, hi = 1.0, 16.0
        u = jax.random.uniform(key, shape, jnp.float32)
        return jnp.log(lo + u * (hi - lo)).astype(dtype)
    if kind == "dt_bias":  # softplus^-1 of dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    raise ValueError(f"unknown init {init!r}")


_draw_fused = jax.jit(_draw, static_argnums=(1, 2, 3))


def is_info(x) -> bool:
    return isinstance(x, ParamInfo)


def init_from_schema(schema: Pytree, key: jax.Array) -> Pytree:
    """Initialize a parameter pytree from a schema; keys derived per-leaf."""
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_info)
    keys = jax.random.split(key, len(leaves))
    out = [info.initialize(k) for info, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def specs_from_schema(schema: Pytree) -> Pytree:
    return jax.tree.map(lambda i: i.spec, schema, is_leaf=is_info)


def abstract_from_schema(schema: Pytree) -> Pytree:
    return jax.tree.map(
        lambda i: jax.ShapeDtypeStruct(i.shape, i.dtype), schema, is_leaf=is_info
    )


def param_count(schema_or_params: Pytree) -> int:
    def _n(x):
        if is_info(x):
            return int(np.prod(x.shape)) if x.shape else 1
        return int(np.prod(x.shape)) if hasattr(x, "shape") else 0

    return sum(_n(l) for l in jax.tree.leaves(schema_or_params, is_leaf=is_info))


def param_bytes(schema: Pytree) -> int:
    def _b(i: ParamInfo):
        return int(np.prod(i.shape)) * jnp.dtype(i.dtype).itemsize

    return sum(_b(l) for l in jax.tree.leaves(schema, is_leaf=is_info))


# ---------------------------------------------------------------------------
# sharding helpers


def shard_if_divisible(dim: int, axis: Optional[str], mesh_axis_sizes: dict) -> Optional[str]:
    """Return `axis` if `dim` divides evenly over it on every mesh we target."""
    if axis is None:
        return None
    size = mesh_axis_sizes.get(axis, 1)
    return axis if dim % size == 0 else None


# Mesh axis sizes we must remain divisible under (the production meshes).
PRODUCTION_AXES = {"data": 32, "model": 16}  # data worst case = pod*data = 32


def mk_spec(*axes) -> P:
    return P(*axes)


def sanitize_specs(specs: Pytree, abstracts: Pytree, mesh) -> Pytree:
    """Drop sharding-axis entries whose mesh size doesn't divide the dim.
    Keeps every spec valid on the given mesh (e.g. kv_heads=8 on model=16
    falls back to replication; batch=1 long-decode drops the data axis)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _fix(spec: P, aval) -> P:
        out = []
        for d, entry in enumerate(spec):
            if entry is None:
                out.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in axes:
                total *= sizes.get(a, 1)
            out.append(entry if aval.shape[d] % total == 0 else None)
        return P(*out)

    return jax.tree.map(
        lambda s, a: _fix(s, a) if isinstance(s, P) else s,
        specs,
        abstracts,
        is_leaf=lambda x: isinstance(x, P),
    )


def pad_vocab(v: int, multiple: int = 2048) -> int:
    return ((v + multiple - 1) // multiple) * multiple
