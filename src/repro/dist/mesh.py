"""Mesh construction for every layer of the stack (the one place it happens).

Production target (TPU v5e):

    single pod:  16 x 16 = 256 chips, axes (data, model)
    multi-pod:   2 x 16 x 16 = 512 chips, axes (pod, data, model) — pure DP
                 across "pod" (the DropCompute All-Reduce domain spans pods).

Everything here is a function, never a module-level constant: importing
this module must not touch jax device state (the dry-run sets XLA_FLAGS
before the first backend init).

Every mesh is built with Auto axis types, so the compiler propagates
shardings from the committed inputs and the constraints the model code
places (``models.transformer.constrain_activations``).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str], *, devices=None
):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_devices: Optional[int] = None, model_parallel: int = 1):
    """Small (data, model) mesh over whatever devices exist (CPU / laptops)."""
    n = n_devices or len(jax.devices())
    assert n % model_parallel == 0, (n, model_parallel)
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


# ---------------------------------------------------------------------------
# Axis arithmetic shared by the sharding rules and the step builders
# ---------------------------------------------------------------------------

DATA_AXES: Tuple[str, ...] = ("pod", "data")  # batch is sharded over these


def axes_size(mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)
    return n


def dp_axes(mesh) -> Tuple[str, ...]:
    """The mesh's data-parallel axes, outermost first."""
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def dp_size(mesh) -> int:
    """Total data parallelism (the DropCompute worker count W)."""
    return axes_size(mesh, DATA_AXES)


def tp_size(mesh) -> int:
    return mesh.shape.get("model", 1)


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
HW = {
    "name": "tpu_v5e",
    "peak_flops_bf16": 197e12,  # FLOP/s
    "hbm_bandwidth": 819e9,  # B/s
    "ici_link_bandwidth": 50e9,  # B/s per link
}
