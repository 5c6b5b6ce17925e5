"""Analytic roofline terms for the four Pallas kernels.

One home for the hardware peaks (``benchmarks/roofline.py`` and
``benchmarks/hillclimb.py`` import them from here) plus per-kernel
FLOP/byte models so the autotuner can record the *achieved-vs-roofline
fraction* next to every winner it caches:

    bound_s  = max(flops / peak_flops, bytes / hbm_bw)
    fraction = bound_s / measured_s

The peaks are those of the device the time was measured on, looked up by
its ``device_kind``; a kind missing from :data:`PEAKS` is an error, not a
default. Pallas interpret mode runs on the host, so its times have no
roofline: they record ``None``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Optional

import jax.numpy as jnp

__all__ = [
    "Peaks",
    "PEAKS",
    "V5E",
    "peaks_for",
    "kernel_flops_bytes",
    "roofline_fraction",
]


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator generation."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    ici_link_bw: float  # chip-to-chip bytes/s over one link
    source: str


# keyed by ``jax.Device.device_kind``
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12,
        hbm_bw=819e9,
        ici_link_bw=50e9,  # 1,600 Gbit/s per chip over 4 links
        source='Google Cloud documentation, "TPU v5e"',
    ),
}
V5E = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises on a kind with no published row."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def kernel_flops_bytes(kernel: str, shape: Mapping[str, int], dtype) -> tuple[float, float]:
    """(flops, hbm_bytes) of one logical kernel invocation.

    Shapes use the same field names as the tuning-cache keys (see
    ``repro.tune.tuner.SHAPE_FIELDS``). The models count the logical
    (unpadded) problem: 2mnk GEMM FLOPs, one HBM touch per operand —
    a *ceiling*, which is exactly what a roofline fraction wants.
    """
    s = {k: int(v) for k, v in shape.items()}
    isz = jnp.dtype(dtype).itemsize
    if kernel == "masked_matmul":
        m, k, n, r, c = s["m"], s["k"], s["n"], s["r"], s["c"]
        flops = 2.0 * m * k * n + k * n  # GEMM + the fused mask multiply
        byts = (m * k + k * n + m * n) * isz + r * c * 4
        return flops, byts
    if kernel == "flash_attention":
        b, hq, sq, skv, d = s["b"], s["hq"], s["sq"], s["skv"], s["d"]
        causal = s.get("causal", 1)
        flops = 4.0 * b * hq * sq * skv * d  # qk^T + pv
        if causal and sq == skv:
            flops /= 2.0  # masked half of the score matrix never lands
        byts = (b * hq * sq * d * 2 + b * s["hkv"] * skv * d * 2) * isz
        return flops, byts
    if kernel == "decode_attention":
        b, hq, hkv, skv, d = s["b"], s["hq"], s["hkv"], s["skv"], s["d"]
        flops = 4.0 * b * hq * skv * d
        # int8 K/V + f32 scales dominate; q and out are one token
        byts = 2.0 * b * hkv * skv * (d + 4) + 2.0 * b * hq * d * 4
        return flops, byts
    if kernel == "mamba_scan":
        b, length, d, n = s["b"], s["l"], s["d"], s["n"]
        # per (token, channel): dA=exp(dt*A) (~2n), dB*u (~2n), h update
        # (~2n), y=C.h (~2n) + D skip
        flops = b * length * d * (8.0 * n + 2.0)
        byts = (4.0 * b * length * d + 2.0 * b * length * n) * isz + d * n * 4 + d * 4
        return flops, byts
    raise ValueError(f"unknown kernel {kernel!r}")


def roofline_fraction(
    flops: float, hbm_bytes: float, measured_s: float, device_kind: Optional[str]
) -> Optional[float]:
    """Fraction of the compute/memory roofline of ``device_kind`` that the
    measured time achieves (1.0 = running exactly at the analytic bound;
    small = headroom). ``device_kind=None`` marks a time taken in Pallas
    interpret mode, which has no roofline: the result is None."""
    if device_kind is None:
        return None
    peaks = peaks_for(device_kind)
    if measured_s <= 0:
        return 0.0
    bound_s = max(flops / peaks.flops, hbm_bytes / peaks.hbm_bw)
    return bound_s / measured_s
