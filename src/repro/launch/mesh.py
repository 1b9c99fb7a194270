"""Production mesh construction and per-chip peak figures.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import (see launch/dryrun.py); tests and benchmarks see 1 device.
"""
from __future__ import annotations

from typing import Dict

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (sharding propagated by the
    compiler, constrained by the logical-axis rules)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names):
    """`jax.shard_map` manual over `axis_names` only (the other mesh axes
    stay Auto), without the varying-manual-axes check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=False)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1, *, pod: int = 0):
    """Small mesh for CPU tests (fits in however many devices exist)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


# Per-chip peaks keyed by `jax.Device.device_kind`.
# "TPU v5 lite" is TPU v5e. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect (4 links of 50 GB/s). The cross-pod DCN share per chip is
# an assumption of the roofline model, not a published figure.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,     # FLOP/s
        "hbm_bw": 819e9,               # B/s
        "ici_bw": 50e9,                # B/s per link
        "dcn_bw": 6.25e9,              # B/s per chip across pods (assumed)
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak figures of one chip; an unknown device is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
