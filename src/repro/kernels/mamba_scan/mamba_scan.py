"""Pallas TPU kernel: chunked Mamba-1 selective scan.

Naive XLA lowering either materializes (B, L, D, N) intermediates (HBM
disaster) or runs an L-step scan with per-step HBM round-trips. The TPU
rethink: grid (B, D/bd, L/bl) with L innermost; the running state h (bd, N)
starts from the given ``h0`` (a chunk continues the one before it) and
lives in VMEM scratch across the whole L sweep, each grid step streams one
(bl, bd) chunk of u/dt and (bl, N) of B/C through VMEM, runs the recurrence
sequentially in-register (VPU) one sublane tile of timesteps at a time, and
writes the (bl, bd) output chunk. HBM
traffic is exactly one read of the inputs and ``h0`` + one write of y and
the final state — the roofline floor for this bandwidth-bound op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import grid_for, resolve_interpret, tpu_compiler_params


def _kernel(
    u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref, hlast_ref, h_ref,
    *, bl: int, nl: int, rows: int,
):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    a = a_ref[...].astype(jnp.float32)  # (bd, N)
    dskip = d_ref[...].astype(jnp.float32)  # (1, bd)

    def tile(g, h):
        # Mosaic reads and writes whole sublane tiles: load ``rows``
        # timesteps at an aligned offset, step through them in registers,
        # and store the outputs back as one tile
        t0 = pl.multiple_of(g * rows, rows)
        span = pl.ds(t0, rows)
        u = u_ref[0, span].astype(jnp.float32)  # (rows, bd)
        dt = dt_ref[0, span].astype(jnp.float32)  # (rows, bd)
        bm = b_ref[0, span].astype(jnp.float32)  # (rows, N)
        cm = c_ref[0, span].astype(jnp.float32)  # (rows, N)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

        def step(j, carry):
            h, y = carry
            pick = lambda x: jnp.sum(jnp.where(row_ids == j, x, 0.0), axis=0)
            u_t, dt_t, b_t, c_t = pick(u), pick(dt), pick(bm), pick(cm)
            da = jnp.exp(dt_t[:, None] * a)  # (bd, N)
            h = da * h + (dt_t * u_t)[:, None] * b_t[None, :]
            y_t = jnp.sum(h * c_t[None, :], axis=1) + dskip[0] * u_t
            return h, jnp.where(row_ids == j, y_t[None, :], y)

        h, y = jax.lax.fori_loop(0, rows, step, (h, jnp.zeros(u.shape, jnp.float32)))
        y_ref[0, span] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bl // rows, tile, h_ref[...])
    h_ref[...] = h

    @pl.when(il == nl - 1)
    def _store_final():
        hlast_ref[0] = h


def sublane_rows(dtype) -> int:
    """Rows in one native sublane tile of ``dtype`` (8 for 32-bit, 16 for
    16-bit): the scan's time block must be a multiple of it."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


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
    rows = sublane_rows(u.dtype)
    if bl % rows:
        raise ValueError(f"time block {bl} is not a multiple of {rows} rows")
    (nd, nl) = grid_for((dim, length), (bd, bl))
    grid = (bsz, nd, nl)
    d2 = d.reshape(1, dim)
    if h0 is None:
        h0 = jnp.zeros((bsz, dim, n), jnp.float32)

    kernel = functools.partial(_kernel, bl=bl, nl=nl, rows=rows)
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
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u, dt, a, b, c, d2, h0.astype(jnp.float32))
    return y, hlast
