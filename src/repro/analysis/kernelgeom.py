"""Pallas kernel geometry lint (KRN001–KRN004) — launch checks before launch.

A Pallas call with bad geometry fails at Mosaic compile/launch time, i.e.
the first time a traffic shape hits it in production. Every failure mode is
a pure function of static geometry, so this pass checks it at lint time:

* KRN001 — a grid axis' dim is not divisible by its block (the exact
  ``grid_for`` failure), a masked-matmul block is incompatible with the
  fault-mask period (the exact ``_mask_axis_plan`` failure), or an in/out
  block breaks Mosaic's tiling rule: its last two dims must be multiples of
  (8, 128) or equal the array's own (the rule the TPU lowering enforces);
* KRN002 — the analytic VMEM footprint of the launch's resident blocks
  (``kernels/common.py::vmem_footprint``) exceeds ``VMEM_LIMIT_BYTES``;
* KRN003 — a degenerate grid: non-positive or int32-overflowing axis;
* KRN004 — a batched ``FaultContext`` would reach a masked GEMM outside
  ``jax.vmap`` (the static form of ``core/masking.py``'s runtime guard,
  via ``context_leak_reason`` — works on abstract contexts).

The ``*_launch`` builders reproduce the geometry the ``ops.py`` wrappers
compute for given logical shapes (same ``choose_block``/padding calls), so
linting the shipped stack means building its launches and running
:func:`check_launch` on each; golden tests hand-build broken launches.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.core.masking import FaultContext, context_leak_reason
from repro.kernels.common import (
    MAX_GRID_AXIS,
    VMEM_LIMIT_BYTES,
    choose_block,
    pad_to_multiple,
    vmem_footprint,
)
from repro.kernels.mamba_scan.mamba_scan import sublane_rows
from repro.kernels.masked_matmul.masked_matmul import _mask_axis_plan

__all__ = [
    "KernelLaunch",
    "check_launch",
    "masked_matmul_launch",
    "flash_attention_launch",
    "decode_attention_launch",
    "mamba_scan_launch",
    "lint_kernels",
    "tiling_violation",
]

_LANES = 128  # TPU lane width: the attention kernels' stats-scratch columns


@dataclass(frozen=True)
class KernelLaunch:
    """Static description of one pallas_call: grid geometry + VMEM blocks.

    ``dims``/``blocks`` are the gridded axes (post-padding dims, in grid
    order); ``vmem_blocks`` is every VMEM-resident buffer of one program
    instance as ``(shape, dtype)``, ``(shape, dtype, is_io)`` or, for an
    in/out block, ``(shape, dtype, True, array_shape)`` — in/out blocks
    plus scratch; ``is_io=False`` marks scratch buffers the Mosaic
    pipeline does NOT double-buffer (see ``vmem_footprint``), and
    ``array_shape`` is the operand the block tiles, checked against
    Mosaic's tiling rule (:func:`tiling_violation`).
    ``mask_blocks`` are ``(block, period)`` pairs for periodic-mask axes
    (masked matmul); ``ctx`` is the FaultContext the launch would consume.
    """

    kernel: str
    dims: tuple
    blocks: tuple
    vmem_blocks: tuple  # ((shape, dtype), ...)
    mask_blocks: tuple = ()  # ((block, period), ...)
    ctx: Optional[FaultContext] = None

    @property
    def grid(self) -> tuple:
        return tuple(
            d // b if b else 0 for d, b in zip(self.dims, self.blocks)
        )


def tiling_violation(block: Sequence[int], array: Sequence[int]) -> Optional[str]:
    """Why Mosaic refuses ``block`` (rank >= 2) as a BlockSpec over
    ``array``, or None.

    The TPU lowering takes a block whose last dim is a multiple of 128 or
    the array's last dim, and whose second-last dim is a multiple of 8 or
    the array's second-last dim."""
    block, array = tuple(int(b) for b in block), tuple(int(a) for a in array)
    ok_lane = block[-1] == array[-1] or block[-1] % 128 == 0
    ok_sublane = block[-2] == array[-2] or block[-2] % 8 == 0
    if ok_lane and ok_sublane:
        return None
    return (
        f"block {block} of array {array}: the last two block dims must be "
        "multiples of (8, 128) or equal the array's"
    )


def check_launch(launch: KernelLaunch) -> list:
    """All geometry findings for one launch (empty list = launchable)."""
    findings: list = []
    name = launch.kernel
    for axis, (d, b) in enumerate(zip(launch.dims, launch.blocks)):
        if b <= 0 or d <= 0:
            findings.append(
                Finding(
                    code="KRN003",
                    entry_point=name,
                    subject=f"axis{axis}",
                    message=f"degenerate grid axis {axis}: dim {d}, block {b}",
                )
            )
            continue
        if d % b:
            findings.append(
                Finding(
                    code="KRN001",
                    entry_point=name,
                    subject=f"axis{axis}",
                    message=(
                        f"grid axis {axis}: dim {d} not divisible by block {b} "
                        "— pallas_call would read out of bounds / grid_for "
                        "raises at launch"
                    ),
                )
            )
            continue
        if d // b > MAX_GRID_AXIS:
            findings.append(
                Finding(
                    code="KRN003",
                    entry_point=name,
                    subject=f"axis{axis}",
                    message=f"grid axis {axis} extent {d // b} overflows int32",
                )
            )
    for i, (b, period) in enumerate(launch.mask_blocks):
        try:
            _mask_axis_plan(int(b), int(period))
        except ValueError as e:
            findings.append(
                Finding(
                    code="KRN001",
                    entry_point=name,
                    subject=f"mask_axis{i}",
                    message=f"mask-period incompatibility: {e}",
                )
            )
    for i, entry in enumerate(launch.vmem_blocks):
        if len(entry) < 4:
            continue
        why = tiling_violation(entry[0], entry[3])
        if why is not None:
            findings.append(
                Finding(
                    code="KRN001",
                    entry_point=name,
                    subject=f"block{i}",
                    message=f"Mosaic tiling rule: {why}",
                )
            )
    vmem = vmem_footprint(launch.vmem_blocks)
    if vmem > VMEM_LIMIT_BYTES:
        findings.append(
            Finding(
                code="KRN002",
                entry_point=name,
                subject="vmem",
                message=(
                    f"resident blocks need {vmem/2**20:.2f} MiB VMEM "
                    f"(limit {VMEM_LIMIT_BYTES/2**20:.0f} MiB) — shrink blocks"
                ),
                bytes=vmem,
            )
        )
    reason = context_leak_reason(launch.ctx)
    if reason is not None:
        findings.append(
            Finding(
                code="KRN004",
                entry_point=name,
                subject="ctx",
                message=reason,
            )
        )
    return findings


# ---------------------------------------------------------------------------
# Launch builders — mirror the ops.py wrappers' geometry exactly
# ---------------------------------------------------------------------------


def masked_matmul_launch(
    m: int,
    k: int,
    n: int,
    mask_shape: tuple,
    *,
    bm: int = 512,
    bn: int = 512,
    bk: int = 512,
    dtype: Any = jnp.float32,
    ctx: Optional[FaultContext] = None,
) -> KernelLaunch:
    """Geometry of ``masked_matmul.ops.masked_matmul(x[(m,k)], w[(k,n)])``."""
    r, c = mask_shape
    bm_ = choose_block(m, bm)
    bn_ = choose_block(n, bn, multiple_of=c)
    bk_ = choose_block(k, bk, multiple_of=r)
    mp, np_ = pad_to_multiple(m, bm_), pad_to_multiple(n, bn_)
    kp = k if k % bk_ == 0 else pad_to_multiple(k, max(bk_, r))
    mask_br = min(bk_, r)
    mask_bc = min(bn_, c)
    return KernelLaunch(
        kernel="masked_matmul",
        dims=(mp, np_, kp),
        blocks=(bm_, bn_, bk_),
        vmem_blocks=(
            ((bm_, bk_), dtype, True, (mp, kp)),  # x block
            ((bk_, bn_), dtype, True, (kp, np_)),  # w block
            ((mask_br, mask_bc), jnp.float32, True, (r, c)),  # mask block
            ((bm_, bn_), dtype, True, (mp, np_)),  # out block
            ((bm_, bn_), jnp.float32, False),  # accumulator scratch
        ),
        mask_blocks=((bk_, r), (bn_, c)),
        ctx=ctx,
    )


def flash_attention_launch(
    batch: int,
    hq: int,
    hkv: int,
    sq: int,
    skv: int,
    head_dim: int,
    *,
    bq: int = 128,
    bkv: int = 128,
    dtype: Any = jnp.float32,
) -> KernelLaunch:
    """Geometry of ``flash_attention.ops.flash_attention`` (B,H,S,D)."""
    bq_ = min(bq, sq)
    sq_p = pad_to_multiple(sq, max(bq_, 8))
    bq_ = min(max(bq_, 8), sq_p)
    bkv_ = min(bkv, skv)
    skv_p = pad_to_multiple(skv, bkv_)
    d = head_dim
    q_arr, kv_arr = (batch * hq, sq_p, d), (batch * hkv, skv_p, d)
    return KernelLaunch(
        kernel="flash_attention",
        dims=(batch * hq, sq_p, skv_p),
        blocks=(1, bq_, bkv_),
        vmem_blocks=(
            ((1, bq_, d), dtype, True, q_arr),  # q block
            ((1, bkv_, d), dtype, True, kv_arr),  # k block
            ((1, bkv_, d), dtype, True, kv_arr),  # v block
            ((1, bq_, d), dtype, True, q_arr),  # out block
            ((bq_, d), jnp.float32, False),  # o accumulator scratch
            ((bq_, _LANES), jnp.float32, False),  # running max scratch
            ((bq_, _LANES), jnp.float32, False),  # running sum scratch
        ),
    )


def decode_attention_launch(
    batch: int,
    hq: int,
    hkv: int,
    skv: int,
    head_dim: int,
    *,
    bkv: int = 128,
    paged: bool = False,
    page_size: int = 0,
) -> KernelLaunch:
    """Geometry of ``decode_attention.ops.decode_attention`` (int8 KV) or
    its paged variant (``paged=True`` with the pool's ``page_size``)."""
    d = head_dim
    group = hq // max(1, hkv)
    if paged:
        gq = 8 * -(-group // 8)
        page = page_size
        q_arr = (batch * hkv, gq, d)
        # the pool's page count does not change the geometry: blocks never
        # tile the page axis, so any count stands in for it
        pages = (hkv, 1, page, d)
        return KernelLaunch(
            kernel="paged_decode_attention",
            dims=(batch * hkv, gq, page),
            blocks=(1, gq, page),
            vmem_blocks=(
                ((1, gq, d), jnp.float32, True, q_arr),  # q block
                ((1, 1, page, d), jnp.int8, True, pages),  # k page
                ((1, 1, page, 1), jnp.float32, True, pages[:3] + (1,)),  # k scales
                ((1, 1, page, d), jnp.int8, True, pages),  # v page
                ((1, 1, page, 1), jnp.float32, True, pages[:3] + (1,)),  # v scales
                ((1, gq, d), jnp.float32, True, q_arr),  # out block
                ((gq, d), jnp.float32, False),  # o accumulator scratch
                ((gq, _LANES), jnp.float32, False),  # running max scratch
                ((gq, _LANES), jnp.float32, False),  # running sum scratch
            ),
        )
    bq = 8  # TPU sublane minimum; decode q is 1 row padded
    skv_p = pad_to_multiple(skv, min(bkv, skv))
    bkv_ = min(bkv, skv_p)
    q_arr, kv_arr = (batch * hq, bq, d), (batch * hkv, skv_p, d)
    scale_arr = (batch * hkv, skv_p, 1)
    return KernelLaunch(
        kernel="decode_attention",
        dims=(batch * hq, bq, skv_p),
        blocks=(1, bq, bkv_),
        vmem_blocks=(
            ((1, bq, d), jnp.float32, True, q_arr),  # q block
            ((1, bkv_, d), jnp.int8, True, kv_arr),  # k block
            ((1, bkv_, 1), jnp.float32, True, scale_arr),  # k scales
            ((1, bkv_, d), jnp.int8, True, kv_arr),  # v block
            ((1, bkv_, 1), jnp.float32, True, scale_arr),  # v scales
            ((1, bq, d), jnp.float32, True, q_arr),  # out block
            ((bq, d), jnp.float32, False),  # o accumulator scratch
            ((bq, _LANES), jnp.float32, False),  # running max scratch
            ((bq, _LANES), jnp.float32, False),  # running sum scratch
        ),
    )


def mamba_scan_launch(
    batch: int,
    length: int,
    dim: int,
    state: int,
    *,
    bd: int = 256,
    bl: int = 128,
    dtype: Any = jnp.float32,
) -> KernelLaunch:
    """Geometry of ``mamba_scan.ops.selective_scan`` (B, L, D) + state N."""
    bd_ = min(bd, dim)
    bl_ = pad_to_multiple(min(bl, length), sublane_rows(dtype))
    dim_p = pad_to_multiple(dim, bd_)
    len_p = pad_to_multiple(length, bl_)
    n = state
    seq, bc = (batch, len_p, dim_p), (batch, len_p, n)
    return KernelLaunch(
        kernel="mamba_scan",
        dims=(batch, dim_p, len_p),
        blocks=(1, bd_, bl_),
        vmem_blocks=(
            ((1, bl_, bd_), dtype, True, seq),  # u block
            ((1, bl_, bd_), dtype, True, seq),  # dt block
            ((bd_, n), jnp.float32, True, (dim_p, n)),  # A block
            ((1, bl_, n), dtype, True, bc),  # B block
            ((1, bl_, n), dtype, True, bc),  # C block
            ((1, bd_), dtype, True, (1, dim_p)),  # D skip
            ((1, bl_, bd_), dtype, True, seq),  # y out
            ((1, bd_, n), jnp.float32, True, (batch, dim_p, n)),  # h_last out
            ((bd_, n), jnp.float32, False),  # h scratch
        ),
    )


def lint_kernels(launches: Sequence[KernelLaunch]) -> tuple[list, dict]:
    """Run :func:`check_launch` over a stack's launches; (findings, stats)."""
    findings: list = []
    stats: dict = {}
    for i, launch in enumerate(launches):
        f = check_launch(launch)
        findings.extend(f)
        key = launch.kernel
        if key in stats:
            key = f"{key}[{i}]"
        stats[key] = dict(
            grid=list(launch.grid),
            vmem_bytes=vmem_footprint(launch.vmem_blocks),
            findings=len(f),
        )
    return findings, stats
