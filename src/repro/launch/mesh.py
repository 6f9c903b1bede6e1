"""Production mesh construction and the per-chip peaks table.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and nothing here may run earlier.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax


# The chip the production mesh is made of; the dry run's roofline reads
# its peaks.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float  # FLOP/s
    hbm_bytes: float  # B
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link (aggregate per-chip traffic is charged at 1 link)
    dcn_bw: float  # B/s per host for the cross-pod axis


# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of interchip interconnect over 4 links (50 GB/s
# each).  The page publishes no data-center-network figure: ``dcn_bw`` is
# the dry run's own assumption.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        hbm_bytes=16e9,
        hbm_bw=819e9,
        ici_bw=50e9,
        dcn_bw=25e9,
    ),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None
