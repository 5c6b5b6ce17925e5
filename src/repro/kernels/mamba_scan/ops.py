"""jit'd public wrapper for the selective scan kernel; falls back to the
lax.scan reference off-TPU. The model layer calls this for train/prefill and
``selective_step_ref`` for single-token decode.

Non-block-multiple (L, D) shapes are zero-padded up to block multiples:
padded steps carry ``u = dt = 0`` so the recurrence is inert there
(``h <- exp(0 * A) * h + 0 = h``) and padded channels are sliced off the
outputs — the wrapper used to silently fall back to whole-axis blocks
instead, losing the chunked VMEM schedule."""
from __future__ import annotations


from repro.kernels.common import is_tpu_backend, pad_axes_to, pad_to_multiple, tuned_block
from repro.kernels.mamba_scan.mamba_scan import selective_scan_pallas, sublane_rows
from repro.kernels.mamba_scan.ref import selective_scan_ref, selective_step_ref


def selective_scan(
    u, dt, a, b, c, d, *, bd: int | None = None, bl: int | None = None, interpret=None
):
    """``bd``/``bl`` default to the tuning cache's winner for this launch
    when one exists, else the 256/128 heuristics (``tuned_block`` seam)."""
    if interpret is None:
        if not is_tpu_backend():
            return selective_scan_ref(u, dt, a, b, c, d)
        interpret = False
    bsz, length, dim = u.shape
    blocks = tuned_block(
        "mamba_scan",
        dict(b=bsz, l=length, d=dim, n=a.shape[1]),
        u.dtype,
        interpret=interpret,
        defaults=dict(bd=256, bl=128),
        overrides=dict(bd=bd, bl=bl),
    )
    bd, bl = blocks["bd"], blocks["bl"]
    bd_ = min(bd, dim)
    bl_ = pad_to_multiple(min(bl, length), sublane_rows(u.dtype))
    dim_p = pad_to_multiple(dim, bd_)
    len_p = pad_to_multiple(length, bl_)
    up = pad_axes_to(u, {1: len_p, 2: dim_p})
    dtp = pad_axes_to(dt, {1: len_p, 2: dim_p})
    ap = pad_axes_to(a, {0: dim_p})
    bp = pad_axes_to(b, {1: len_p})
    cp = pad_axes_to(c, {1: len_p})
    dp = pad_axes_to(d, {0: dim_p})
    y, hlast = selective_scan_pallas(
        up, dtp, ap, bp, cp, dp, bd=bd_, bl=bl_, interpret=interpret
    )
    return y[:, :length, :dim], hlast[:, :dim]


selective_step = selective_step_ref
