"""Production meshes (TPU v5e).

Single pod: (16, 16)  ("data", "model")   — 256 chips.
Multi-pod : (2, 16, 16) ("pod", "data", "model") — 512 chips; the ``pod``
axis is pure data parallelism (its collectives ride DCN, so the sharding
rules place only the gradient all-reduce there).

Functions, not module constants — importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link
}


def make_production_mesh(*, multi_pod: bool = False):
    import os
    override = os.environ.get("REPRO_MESH")  # e.g. "2,2" — test-scale meshes
    if override:
        shape = tuple(int(x) for x in override.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        return _auto_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: the model code places activations
    with ``with_sharding_constraint``, which refuses Explicit axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def make_host_mesh():
    """Degenerate 1-device mesh for smoke tests."""
    return _auto_mesh((1, 1), ("data", "model"))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"


def dp_size(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))
