"""Production mesh definitions.

Defined as FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax
init, and nothing here may run earlier.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto-typed — the one place this
    repo builds a mesh. Since JAX 0.9 ``make_mesh`` defaults each axis to
    ``AxisType.Explicit``, under which sharding propagates through types
    and indexed updates such as ``.at[...].set`` demand an explicit
    ``out_sharding``; every model, EP and serving path here is written
    against Auto (compiler-propagated) sharding."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """v5e pod mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips with a
    leading 'pod' axis (DCN-connected). `shape` overrides the per-pod
    (data, model) factorisation for §Perf mesh-reshape experiments —
    always 256 chips/pod."""
    per_pod = tuple(shape) if shape else (16, 16)
    assert per_pod[0] * per_pod[1] == 256, "a v5e pod is 256 chips"
    mesh_shape = ((2,) + per_pod) if multi_pod else per_pod
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(mesh_shape, axes)


def make_serving_mesh(devices: int | None = None, *, ep: int | None = None,
                      tp: int = 1, data: int = 1):
    """("data", "ep", "tp") mesh for the EP serving hot path.

    `devices` caps how many local devices to use (None = all; run with
    XLA_FLAGS=--xla_force_host_platform_device_count=N to force a
    multi-device CPU host). `ep` defaults to devices // (data * tp).
    The factorisation must use exactly data*ep*tp devices."""
    n = len(jax.devices()) if devices is None else devices
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"make_serving_mesh: {n} devices requested but only {avail} "
            "present — set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n} before the first jax call to force host devices")
    if ep is None:
        if n % (data * tp):
            raise ValueError(
                f"make_serving_mesh: {n} devices do not factor into "
                f"data={data} x ep x tp={tp}")
        ep = n // (data * tp)
    if data * ep * tp != n:
        raise ValueError(
            f"make_serving_mesh: data={data} x ep={ep} x tp={tp} "
            f"!= {n} devices")
    return make_mesh((data, ep, tp), ("data", "ep", "tp"))


def dp_axes(mesh) -> tuple:
    """Logical data-parallel axes (pod is folded into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mp_axis(mesh) -> str:
    return "model"
