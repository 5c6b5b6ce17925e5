"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table: every roofline share and every
``*mfu*`` number divides by these, so no change to the program can move
the yardstick. A device kind that is not here is an error, not a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops: float  # dense bf16 FLOP/s of one chip
    hbm_bw: float  # HBM bytes/s of one chip
    hbm_bytes: int  # HBM capacity of one chip
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 10**9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM at 819 GB/s per chip",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
