"""Production meshes.

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before the first jax call).

Target hardware (roofline constants): TPU v5e — 197 TFLOP/s bf16/chip,
819 GB/s HBM/chip, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 512 if multi_pod else 256
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever host devices exist (tests/examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         (AxisType.Auto,) * 2,
                         devices=jax.devices()[:data * model_axis])


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
