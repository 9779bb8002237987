"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the 'pod'
axis carries pure data parallelism (gradient all-reduce over DCI), 'data' is
the FSDP axis, 'model' the TP/EP axis.

Every mesh is built with ``AxisType.Auto`` axes: the model code places
activations with sharding constraints and lets GSPMD propagate the rest,
which explicit-typed axes (the ``jax.make_mesh`` default) reject at the
embedding gather and the pipeline's output slice.

Functions, not module constants: importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small host-device meshes, e.g. (2,4))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants for the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s/link (~ per-direction)
HBM_PER_CHIP = 16 * 2**30     # 16 GiB
