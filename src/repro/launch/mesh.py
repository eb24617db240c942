"""Production meshes.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests/benches must keep seeing 1 device.

Mesh topology (TPU v5e): a pod is a 16×16 mesh → axes (data=16, model=16);
multi-pod adds the leading ``pod`` axis over the inter-pod DCI links. DP runs
over pod×data, TP/EP over model / data respectively (see
models/partitioning.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding constraints,
    shard_maps and NamedShardings in this repository are written for Auto
    semantics, while ``jax.make_mesh`` defaults to ``Explicit`` axes (which
    ``with_sharding_constraint`` refuses to name)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    if pod > 1:
        return auto_mesh((pod, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))


# Hardware constants (TPU v5e) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (intra-pod)
DCI_BW = 25e9                     # bytes/s per link (inter-pod, conservative)
HBM_BYTES = 16 << 30              # v5e HBM per chip
