"""Mesh construction (functions, not module constants — importing this
module never touches jax device state).

Production target: TPU v5e pods, 256 chips each, 16x16 ICI torus.
Single-pod mesh (16, 16) = ("data", "model"); multi-pod adds a leading
"pod" axis over DCN: (2, 16, 16) = ("pod", "data", "model").
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with Auto axis types (GSPMD-propagated sharding,
    which the k-shard routes and the model's sharding plans rely on).

    The single place the repo constructs meshes.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (CPU) devices the test session has."""
    if pod:
        return make_mesh_compat((pod, data, model), ("pod", "data", "model"))
    return make_mesh_compat((data, model), ("data", "model"))


# TPU v5e hardware constants (per chip) — the roofline denominators.
PEAK_BF16_FLOPS = 197e12        # bf16 MXU
PEAK_INT8_OPS = 394e12          # int8 MXU (the paper's 2x IMMU advantage)
HBM_BW = 819e9                  # bytes/s
ICI_LINK_BW = 50e9              # bytes/s per link
HBM_PER_CHIP = 16 * 2**30       # 16 GiB
