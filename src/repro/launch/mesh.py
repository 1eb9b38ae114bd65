"""Production meshes.

Single pod: 256 TPU v5e chips as ("data", "model") = (16, 16).
Multi-pod:  2 pods = 512 chips as ("pod", "data", "model") = (2, 16, 16).

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """Auto-axis mesh: GSPMD propagates shardings and ``shard_hint``'s
    ``with_sharding_constraint`` applies (Explicit axes reject it)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Tiny mesh for CI-style tests under --xla_force_host_platform_device_count=8."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def client_axes(multi_pod: bool, clients_on_pod_only: bool) -> tuple:
    """Mesh axes the FL client dimension is laid out on (DESIGN.md sec. 3)."""
    if clients_on_pod_only:
        return ("pod",) if multi_pod else ()
    return ("pod", "data") if multi_pod else ("data",)


def num_clients(mesh: jax.sharding.Mesh, clients_on_pod_only: bool) -> int:
    multi_pod = "pod" in mesh.axis_names
    axes = client_axes(multi_pod, clients_on_pod_only)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return max(n, 1)
