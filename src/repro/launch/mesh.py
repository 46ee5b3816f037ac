"""Production mesh construction (assignment-mandated shapes).

Defined as functions so importing this module never touches jax device
state (device count is locked on first jax init — the dry-run sets
XLA_FLAGS before importing anything).

Besides the assignment meshes, :func:`make_serving_mesh` builds the
single-axis ``("data",)`` mesh the sharded serving engine
(``serving.mesh_engine.ShardedEngine``) partitions its batch-of-requests
cache over: one shard of cache rows per device.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "make_serving_mesh", "make_test_mesh"]


def _device_inventory() -> str:
    """Human-readable current device census for error messages."""
    devices = jax.devices()
    kinds: dict = {}
    for d in devices:
        kinds[d.platform] = kinds.get(d.platform, 0) + 1
    census = ", ".join(f"{n}x {k}" for k, n in sorted(kinds.items()))
    return f"{len(devices)} visible ({census})"


def _require_devices(n: int, shape: Tuple[int, ...], axes: Sequence[str]):
    """First ``n`` devices, or a RuntimeError naming the exact remediation.

    The remediation string is the actual flag to export — device count is
    locked on first jax init, so it must land in the environment before any
    jax import (the dry-run and the CI multi-device job both do this).
    """
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {tuple(shape)} with axes "
            f"{tuple(axes)}, have {_device_inventory()}. Remediation: export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"the first jax import (an already-initialized backend cannot "
            f"grow its device count)"
        )
    return devices[:n]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = _require_devices(n, shape, axes)
    return jax.make_mesh(shape, axes, devices=devices)


def make_test_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for multi-device tests (8 host devices)."""
    n = data * model
    devices = _require_devices(n, (data, model), ("data", "model"))
    arr = np.asarray(devices).reshape(data, model)
    return Mesh(arr, ("data", "model"))


def make_serving_mesh(data: int) -> Mesh:
    """One-axis ``("data",)`` mesh of ``data`` devices for row-sharded
    serving: the ShardedEngine splits its batch-of-requests cache's row axis
    across this axis (one shard of rows, one Transport, one contention
    domain per device)."""
    if data < 1:
        raise ValueError(f"make_serving_mesh needs data >= 1, got {data}")
    devices = _require_devices(data, (data,), ("data",))
    return Mesh(np.asarray(devices), ("data",))
