"""Pallas TPU kernel: chunked Mamba-1 selective scan.

Naive XLA lowering either materializes (B, L, D, N) intermediates (HBM
disaster) or runs an L-step scan with per-step HBM round-trips. The TPU
rethink: grid (B, D/bd, L/bl) with L innermost; each grid step streams one
(bl, bd) chunk of u/dt and (bl, N) of B/C through VMEM and writes the
(bl, bd) output chunk, and the running state starts from the given ``h0``
(a chunk continues the one before it) and lives in VMEM scratch across the
whole L sweep. HBM traffic is one read of the inputs and ``h0`` and one
write of y and the final state: the roofline floor of this
bandwidth-bound op.

Layout: channels on the lanes, states on the sublanes. The state is held
as ``h (N, bd)`` and ``A`` as ``(N, bd)``: with N = 16 that is two sublane
tiles of float32 by ``bd / 128`` lane tiles, every lane busy. (Held the
other way round, ``(bd, N)``, 16 of 128 lanes work and each step's output
leaves a cross-lane reduction to relayout.) ``h0``, ``A`` and the final
state are transposed inside the kernel, once per channel block, so the
``(B, D, N)`` state contract is unchanged.

Each tile of ``tb`` timesteps (one sublane tile of the narrowest input)
runs in two phases:

1. off the serial chain, for every step of the tile: ``exp(dt_t A)`` and
   ``(dt_t u_t) B_t`` into ``(tb, N, bd)`` VMEM scratch. ``dt_t``/``u_t``
   are ``(1, bd)`` rows of the loaded tile (broadcast over the states'
   sublanes); ``B_t`` is an ``(N, 1)`` column of the tile's transposed
   ``(N, tb)`` copy (broadcast over the lanes);
2. the recurrence ``h = dA_t h + dBu_t`` and ``y_t = sum_n h C_t``, a
   16-row sublane reduction that lands as a ``(1, bd)`` lane row, written
   to row ``t`` of a ``(tb, bd)`` scratch. The skip ``D u`` is added and
   the tile stored once, after the recurrence.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    VMEM_LIMIT_BYTES,
    grid_for,
    pad_to_multiple,
    resolve_interpret,
    tpu_compiler_params,
)

LANES = 128
MAX_CHANNEL_BLOCK = 1024  # lanes of one channel block (bd)
MAX_TIME_BLOCK = 256  # timesteps of one time block (bl)
_CHAIN_LANES = 512  # lanes of h carried through the recurrence at a time
_VMEM_BUDGET = VMEM_LIMIT_BYTES * 3 // 4  # what a default channel block may take


def _kernel(
    u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref, hlast_ref,
    h_ref, at_ref, da_ref, dbu_ref, ys_ref,
    *, bl: int, nl: int, tb: int,
):
    il = pl.program_id(2)
    bd = h_ref.shape[1]
    chain = bd if bd % LANES else math.gcd(bd, _CHAIN_LANES)

    @pl.when(il == 0)
    def _init():
        h_ref[...] = h0_ref[0].T  # (N, bd)
        at_ref[...] = a_ref[...].astype(jnp.float32).T  # (N, bd)

    dskip = d_ref[...].astype(jnp.float32)  # (1, bd)

    def tile(g, carry):
        t0 = pl.multiple_of(g * tb, tb)
        span = pl.ds(t0, tb)
        u = u_ref[0, span].astype(jnp.float32)  # (tb, bd)
        dt = dt_ref[0, span].astype(jnp.float32)  # (tb, bd)
        x = dt * u
        bt = b_ref[0, span].astype(jnp.float32).T  # (N, tb)
        ct = c_ref[0, span].astype(jnp.float32).T  # (N, tb)
        a = at_ref[...]
        for t in range(tb):
            da_ref[t] = jnp.exp(dt[t:t + 1] * a)
            dbu_ref[t] = x[t:t + 1] * bt[:, t:t + 1]
        for lo in range(0, bd, chain):
            cols = pl.ds(lo, chain)
            h = h_ref[:, cols]
            for t in range(tb):
                h = da_ref[t, :, cols] * h + dbu_ref[t, :, cols]
                ys_ref[t:t + 1, cols] = jnp.sum(h * ct[:, t:t + 1], axis=0, keepdims=True)
            h_ref[:, cols] = h
        y_ref[0, span] = (ys_ref[...] + dskip * u).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bl // tb, tile, 0)

    @pl.when(il == nl - 1)
    def _store_final():
        hlast_ref[0] = h_ref[...].T


def sublane_rows(dtype) -> int:
    """Rows in one native sublane tile of ``dtype`` (8 for 32-bit, 16 for
    16-bit): the scan's time block must be a multiple of it."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def scratch_shapes(bd: int, n: int, tb: int) -> list:
    """The kernel's VMEM scratch, ``(shape, dtype)`` each: the state and
    ``A`` with channels on the lanes, the tile's ``exp(dt A)`` and
    ``(dt u) B``, and the tile's outputs before the skip."""
    f32 = jnp.float32
    return [((n, bd), f32), ((n, bd), f32), ((tb, n, bd), f32), ((tb, n, bd), f32),
            ((tb, bd), f32)]


def _vmem_bytes(bd: int, bl: int, n: int, tb: int) -> int:
    """Double-buffered in/out blocks plus scratch of one grid step, every
    array counted at four bytes an element."""
    io = 3 * bl * bd + 2 * bl * n + (3 * n + 1) * bd
    return 4 * (2 * io + sum(math.prod(s) for s, _ in scratch_shapes(bd, n, tb)))


def scan_blocks(length: int, dim: int, n: int, dtype, *, bd: int | None = None,
                bl: int | None = None) -> tuple[int, int, int, int]:
    """``(bd, bl, dim_p, len_p)`` of a launch over ``(length, dim)`` with
    ``n`` states, ``dtype`` the narrowest input. Given blocks are clamped
    to the axis (and ``bl`` rounded up to the sublane rows); by default
    ``bl`` is up to ``MAX_TIME_BLOCK`` steps and ``bd`` the largest lane
    multiple that divides the lane-padded channels, is at most
    ``MAX_CHANNEL_BLOCK`` and fits the scoped VMEM (the whole axis where
    that is narrower)."""
    rows = sublane_rows(dtype)
    bl_ = pad_to_multiple(min(bl or MAX_TIME_BLOCK, length), rows)
    if bd is None:
        tiles = pad_to_multiple(dim, LANES) // LANES
        bd = LANES * max(
            k for k in range(1, tiles + 1)
            if tiles % k == 0 and (k == 1 or (
                k * LANES <= MAX_CHANNEL_BLOCK
                and _vmem_bytes(k * LANES, bl_, n, rows) <= _VMEM_BUDGET))
        )
    bd_ = min(bd, dim)
    return bd_, bl_, pad_to_multiple(dim, bd_), pad_to_multiple(length, bl_)


@functools.partial(jax.jit, static_argnames=("bd", "bl", "interpret"))
def selective_scan_pallas(
    u: jax.Array,  # (B, L, D)
    dt: jax.Array,  # (B, L, D)
    a: jax.Array,  # (D, N)
    b: jax.Array,  # (B, L, N)
    c: jax.Array,  # (B, L, N)
    d: jax.Array,  # (D,)
    h0: jax.Array | None = None,  # (B, D, N) state to continue from; zeros
    *,
    bd: int = 256,
    bl: int = 128,
    interpret: bool | None = None,
):
    """Returns (y (B, L, D), h_final (B, D, N))."""
    interpret = resolve_interpret(interpret)
    bsz, length, dim = u.shape
    n = a.shape[1]
    bd = min(bd, dim)
    bl = min(bl, length)
    tb = max(sublane_rows(x.dtype) for x in (u, dt, b, c))
    if bl % tb:
        raise ValueError(f"time block {bl} is not a multiple of {tb} rows")
    (nd, nl) = grid_for((dim, length), (bd, bl))
    grid = (bsz, nd, nl)
    d2 = d.reshape(1, dim)
    if h0 is None:
        h0 = jnp.zeros((bsz, dim, n), jnp.float32)

    kernel = functools.partial(_kernel, bl=bl, nl=nl, tb=tb)
    y, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bl, bd), lambda ib, id_, il: (ib, il, id_)),  # u
            pl.BlockSpec((1, bl, bd), lambda ib, id_, il: (ib, il, id_)),  # dt
            pl.BlockSpec((bd, n), lambda ib, id_, il: (id_, 0)),  # a
            pl.BlockSpec((1, bl, n), lambda ib, id_, il: (ib, il, 0)),  # b
            pl.BlockSpec((1, bl, n), lambda ib, id_, il: (ib, il, 0)),  # c
            pl.BlockSpec((1, bd), lambda ib, id_, il: (0, id_)),  # d skip
            pl.BlockSpec((1, bd, n), lambda ib, id_, il: (ib, id_, 0)),  # h0
        ],
        out_specs=[
            pl.BlockSpec((1, bl, bd), lambda ib, id_, il: (ib, il, id_)),
            pl.BlockSpec((1, bd, n), lambda ib, id_, il: (ib, id_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, length, dim), u.dtype),
            jax.ShapeDtypeStruct((bsz, dim, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(s, dt_) for s, dt_ in scratch_shapes(bd, n, tb)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u, dt, a, b, c, d2, h0.astype(jnp.float32))
    return y, hlast
