"""Production mesh construction.

Functions, not module-level constants — importing this module never touches
jax device state (device count locks on first jax init, and smoke tests
must see 1 device while the dry-run sees 512 placeholders).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the step builders place arrays
    with ``with_sharding_constraint`` (``parallel.ctx``), which refuses
    the Explicit axes ``make_mesh`` creates by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """v5e production topology: 16×16 = 256 chips/pod; 2 pods via DCN.

    Single-pod: ("data", "model") — FSDP/DP × TP(+EP+SP).
    Multi-pod:  ("pod", "data", "model") — 'pod' extends data parallelism
    (hierarchical gradient reduction over the DCN-class axis) or hosts
    pipeline stages (parallel/pipeline).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: Optional[int] = None) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    model = min(model, n)
    data = data or (n // model)
    return _auto_mesh((data, model), ("data", "model"))


def mesh_desc(mesh: Mesh) -> dict:
    return {"axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "n_devices": int(mesh.devices.size)}
