"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

The benchmark's own copy: the yardstick every share of a peak or a roofline
is measured against. A kind that is not here is an error, never a default.
"""
from __future__ import annotations

import dataclasses


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


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {device_kind!r}"
                         f" (known: {sorted(PEAKS)})") from None
