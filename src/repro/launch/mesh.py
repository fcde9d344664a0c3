"""Production meshes.

Single pod: (data=16, model=16) — 256 TPU v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis is
the slow-link (DCI) analogue of the paper's Internet links — batch/FSDP
traffic stays inside a pod, only gradient all-reduce crosses pods.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: sharding is propagated by GSPMD
    from ``with_sharding_constraint`` and the shard_map specs, which the
    default Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_stages: int = 0):
    """Small mesh over however many (host) devices exist — used by the
    pipelined-executor example, not by the dry-run."""
    n = len(jax.devices())
    stages = n_stages or n
    return _auto_mesh((stages,), ("stage",))


def data_axes(mesh) -> tuple:
    """Axes used for batch/data parallelism ('pod' joins 'data' if present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh) -> str:
    return "model"
