"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis carries
pure data parallelism across the inter-pod DCN/ICI boundary, so gradient
all-reduces hierarchically decompose (intra-pod ring + inter-pod exchange).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Dict

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    # Auto axes: shardings propagate through the compiler (GSPMD), which
    # the models' param/cache specs are written for
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_shards: int = 1, devices=None):
    """Mesh over ``devices`` (default: every device this host sees):
    (data, model) with the requested model sharding (``plan_cluster``)."""
    devices = list(devices) if devices is not None else jax.devices()
    from repro.core.replicate import plan_cluster
    plan = plan_cluster(len(devices), model_shards)
    n = plan.mesh_shape[0] * plan.mesh_shape[1]
    return _mesh(plan.mesh_shape, ("data", "model"), devices=devices[:n])


# Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links, taken here
# as 50 GB/s per link direction).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}
# the chip the production meshes above are built from
PRODUCTION_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; a kind without a
    row raises rather than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row to PEAKS with its "
                       f"source") from None
