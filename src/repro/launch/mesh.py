"""Production mesh builders (TPU v5e; 256 chips/pod).

A FUNCTION, not a module-level constant — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto: shardings follow the specs
    the caller attaches. Activate it with ``jax.set_mesh``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU-host tests (needs XLA host platform devices)."""
    return make_mesh((data, model), ("data", "model"))


# Hardware constants for the roofline analysis (assignment-provided).
TPU_V5E = {
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link
    "hbm_bytes": 16 << 30,
    "chips_per_pod": 256,
}
