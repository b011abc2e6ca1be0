"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (dry-run sets the 512-device flag first).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); `pod` is an
outer data axis (DCN between pods, ICI within).

All constructors validate through :func:`checked_mesh` and raise the
typed ``repro.sharding.mesh.MeshConfigError`` (a ValueError) on bad
axis sizes or device-count mismatches, with a message naming the fix —
instead of whatever jax.make_mesh happens to throw.  The MSR storage
layer's 1-D stream mesh lives in ``repro.sharding.mesh.StreamMesh``
(DESIGN.md §14); these are the LM-launch meshes.
"""
from __future__ import annotations

import math

import jax

from repro.sharding.mesh import MeshConfigError


def checked_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with typed validation: every axis size a positive
    int, one name per axis, and the total device product available."""
    if len(shape) != len(axes):
        raise MeshConfigError(
            f"mesh shape {shape} has {len(shape)} axes but {len(axes)} "
            f"names {axes}")
    if len(set(axes)) != len(axes):
        raise MeshConfigError(f"duplicate mesh axis names: {axes}")
    for size, name in zip(shape, axes):
        if isinstance(size, bool) or not isinstance(size, int) or size < 1:
            raise MeshConfigError(
                f"mesh axis {name!r} must have a positive int size, "
                f"got {size!r}")
    want = math.prod(shape)
    have = len(jax.devices())
    if want > have:
        raise MeshConfigError(
            f"mesh {dict(zip(axes, shape))} needs {want} devices but only "
            f"{have} are available; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={want} BEFORE the "
            f"first jax import")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return checked_mesh(shape, axes)


def make_storage_mesh(n_nodes: int):
    """1-D ring mesh for the MSR storage layer (circulant encode/repair runs
    neighbour-wise over this axis — DESIGN.md §2)."""
    return checked_mesh((n_nodes,), ("storage",))


def make_host_mesh():
    """Whatever this host offers (tests/examples): 1-D data mesh."""
    n = len(jax.devices())
    return checked_mesh((n,), ("data",))
