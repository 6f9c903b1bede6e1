"""Launchers: production mesh, multi-pod dry-run, roofline analysis,
elastic training and batched serving CLIs."""

from .mesh import PEAKS, PRODUCTION_DEVICE_KIND, ChipPeaks, make_production_mesh, peaks_for

__all__ = [
    "PEAKS",
    "PRODUCTION_DEVICE_KIND",
    "ChipPeaks",
    "make_production_mesh",
    "peaks_for",
]
