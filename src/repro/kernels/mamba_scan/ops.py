"""jit'd public wrapper for the selective scan kernel; falls back to the
lax.scan reference off-TPU. The model layer calls this for train/prefill and
``selective_step_ref`` for single-token decode.

Non-block-multiple (L, D) shapes are zero-padded up to block multiples:
padded steps carry ``u = dt = 0`` so the recurrence is inert there
(``h <- exp(0 * A) * h + 0 = h``) and padded channels are sliced off the
outputs — the wrapper used to silently fall back to whole-axis blocks
instead, losing the chunked VMEM schedule.

``h0`` (``(B, D, N)``, default zeros) is the state the scan continues
from: a prompt streamed in chunks carries each chunk's final state into
the next, and splitting a scan anywhere reproduces the unsplit one."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import is_tpu_backend, pad_axes_to, tuned_block
from repro.kernels.mamba_scan.mamba_scan import scan_blocks, selective_scan_pallas
from repro.kernels.mamba_scan.ref import selective_scan_ref, selective_step_ref


def selective_scan(
    u, dt, a, b, c, d, h0=None, *, bd: int | None = None, bl: int | None = None,
    interpret=None,
):
    """``bd``/``bl`` default to the tuning cache's winner for this launch
    when one exists, else to blocks chosen from the shape
    (``scan_blocks``; ``tuned_block`` seam)."""
    if interpret is None:
        if not is_tpu_backend():
            return selective_scan_ref(u, dt, a, b, c, d, h0)
        interpret = False
    bsz, length, dim = u.shape
    n = a.shape[1]
    narrow = min((u.dtype, dt.dtype, b.dtype, c.dtype), key=lambda t: jnp.dtype(t).itemsize)
    heuristic = scan_blocks(length, dim, n, narrow)
    blocks = tuned_block(
        "mamba_scan",
        dict(b=bsz, l=length, d=dim, n=n),
        u.dtype,
        interpret=interpret,
        defaults=dict(bd=heuristic[0], bl=heuristic[1]),
        overrides=dict(bd=bd, bl=bl),
    )
    bd_, bl_, dim_p, len_p = scan_blocks(length, dim, n, narrow, **blocks)
    up = pad_axes_to(u, {1: len_p, 2: dim_p})
    dtp = pad_axes_to(dt, {1: len_p, 2: dim_p})
    ap = pad_axes_to(a, {0: dim_p})
    bp = pad_axes_to(b, {1: len_p})
    cp = pad_axes_to(c, {1: len_p})
    dp = pad_axes_to(d, {0: dim_p})
    h0p = None if h0 is None else pad_axes_to(h0, {1: dim_p})
    y, hlast = selective_scan_pallas(
        up, dtp, ap, bp, cp, dp, h0p, bd=bd_, bl=bl_, interpret=interpret
    )
    return y[:, :length, :dim], hlast[:, :dim]


selective_step = selective_step_ref
