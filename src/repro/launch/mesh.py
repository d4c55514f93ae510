"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) data×model (256 v5e chips).
Multi-pod: (2, 16, 16) pod×data×model (512 chips); the `pod` axis carries
pure data parallelism across the ICI-disjoint pods (gradient all-reduce
crosses DCI once per step).
"""
from __future__ import annotations

import jax

from repro.models.layers import MeshAxes


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (sharding propagated by XLA);
    JAX now defaults mesh axes to Explicit."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(mesh, *, fsdp: bool = True) -> MeshAxes:
    names = mesh.axis_names
    data = ("pod", "data") if "pod" in names else ("data",)
    return MeshAxes(data=data, model="model" if "model" in names else None, fsdp=fsdp)


def make_test_mesh(data: int = 1, model: int = 1):
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(*, tp: int = 1, dp: int = 1, pp: int = 1):
    """Mesh for the sharded decode serving paths over the devices of the
    current backend. ``tp``/``dp`` build a ``(data, model)`` mesh for
    tensor-parallel decode (``ShardedDecodeRunner``); ``pp`` builds a
    1-D ``(stage,)`` mesh for exit-gated pipeline decode windows — the
    two are alternative layouts, not composable on one mesh here."""
    if pp > 1:
        if tp > 1 or dp > 1:
            raise ValueError("pp is a (stage,) mesh; combine with tp/dp "
                             "by nesting runners, not one mesh")
        need, shape, axes = pp, (pp,), ("stage",)
    else:
        need, shape, axes = dp * tp, (dp, tp), ("data", "model")
    n = len(jax.devices())
    if n < need:
        raise ValueError(
            f"mesh {shape} needs {need} devices, backend has {n} — on CPU "
            "export XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before the process starts")
    return make_mesh(shape, axes)
