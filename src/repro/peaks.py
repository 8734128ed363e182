"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

The one place that says how fast a chip is: the planner's
``HardwareSpec.detect`` and the dry-run roofline read their figures from
here. An accelerator that is not
in the table is an error, never a default; the CPU has no entry because no
device metric is taken on it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float  # dense bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    hbm_bytes: float  # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2 at 819 GB/s per chip"),
}


def peaks_for(device) -> Optional[ChipPeaks]:
    """``device``'s published peaks; None for a CPU device."""
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device.device_kind!r} "
            f"(platform {device.platform}); add them to repro.peaks.PEAKS "
            f"(known: {sorted(PEAKS)})") from None
