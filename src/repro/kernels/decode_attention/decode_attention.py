"""Pallas TPU kernel: decode attention over an int8-quantized KV cache.

Decode is memory-bound on every assigned arch (EXPERIMENTS.md §Roofline):
the per-token cost is dominated by streaming the KV cache through HBM.
Storing KV as int8 with per-(head, position) scales halves that traffic —
but only if the dequantize happens in VMEM between the DMA and the MXU;
an XLA-level dequant materializes a bf16 copy and makes traffic WORSE
(int8 read + bf16 write + bf16 read). This kernel fuses it:

grid (B*Hq, S/bkv), kv innermost; each step DMAs an int8 (bkv, D) block +
its scales, dequantizes in VMEM, and runs the online-softmax update. q (one
token, padded to 8 sublanes) stays resident. The scales travel as a trailing
unit axis, (bkv, 1) blocks: Mosaic takes a block whose last two dims are
multiples of (8, 128) or the array's own, and a (bkv,) row of a (rows, S)
array is neither.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import grid_for, resolve_interpret, tpu_compiler_params

LANES = 128
NEG_INF = -1e30


def _kernel(
    len_ref,  # scalar prefetch: (1,) int32 valid kv length
    q_ref,  # (1, bq, d)
    k_ref,  # (1, bkv, d) int8
    ks_ref,  # (1, bkv, 1) f32
    v_ref,
    vs_ref,
    o_ref,  # (1, bq, d)
    acc_ref,
    m_ref,
    l_ref,
    *,
    nk: int,
    bq: int,
    bkv: int,
    scale: float,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[0]
    kv_start = ik * bkv

    @pl.when(kv_start < valid)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32) * ks_ref[0]  # dequant in VMEM
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bkv)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(cols < valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        v = v_ref[0].astype(jnp.float32) * vs_ref[0]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _paged_kernel(
    tbl_ref,  # scalar prefetch: (B, maxp) int32 block tables
    len_ref,  # scalar prefetch: (B,) int32 valid kv lengths
    q_ref,  # (1, gq, d) — one kv-head's query group, padded to >= 8 sublanes
    k_ref,  # (1, 1, page, d) int8 — the page picked by the block table
    ks_ref,  # (1, 1, page, 1) f32
    v_ref,
    vs_ref,
    o_ref,  # (1, gq, d)
    acc_ref,
    m_ref,
    l_ref,
    *,
    maxp: int,
    gq: int,
    page: int,
    hkv: int,
    scale: float,
):
    bh = pl.program_id(0)  # flattened (sequence, kv head)
    ip = pl.program_id(1)  # position in the page chain

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[bh // hkv]
    kv_start = ip * page

    @pl.when(kv_start < valid)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]  # dequant in VMEM
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (gq, page)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (gq, page), 1)
        s = jnp.where(cols < valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ip == maxp - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("hkv", "scale", "gq", "interpret"),
)
def paged_decode_attention_pallas(
    q: jax.Array,  # (B*Hkv, gq, D) — per-kv-head query groups, gq >= 8
    k_pages_i8: jax.Array,  # (Hkv, P, page, D) int8 page pool
    k_scale: jax.Array,  # (Hkv, P, page) f32
    v_pages_i8: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, maxp) int32 page ids
    seq_lens: jax.Array,  # (B,) int32 valid kv lengths
    *,
    hkv: int,
    scale: Optional[float] = None,
    gq: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode attention straight off the paged pool: the block table rides
    ahead of the DMAs as a scalar-prefetch operand, so grid step (bh, i)
    fetches page ``block_tables[b, i]`` — the gather never materializes a
    dense per-sequence cache in HBM. Online softmax over the page chain;
    dequantization stays fused in VMEM like the dense kernel above."""
    interpret = resolve_interpret(interpret)
    bh, gq_, d = q.shape
    _, _, page, _ = k_pages_i8.shape
    maxp = block_tables.shape[1]
    assert gq_ == gq
    scale = scale if scale is not None else 1.0 / (d**0.5)
    grid = (bh, maxp)

    q_map = lambda bh_, i, tbl, lens: (bh_, 0, 0)
    kv_map = lambda bh_, i, tbl, lens: (bh_ % hkv, tbl[bh_ // hkv, i], 0, 0)
    s_map = lambda bh_, i, tbl, lens: (bh_ % hkv, tbl[bh_ // hkv, i], 0, 0)

    kernel = functools.partial(
        _paged_kernel, maxp=maxp, gq=gq, page=page, hkv=hkv, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block tables + lengths ride ahead
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, gq, d), q_map),
                pl.BlockSpec((1, 1, page, d), kv_map),
                pl.BlockSpec((1, 1, page, 1), s_map),
                pl.BlockSpec((1, 1, page, d), kv_map),
                pl.BlockSpec((1, 1, page, 1), s_map),
            ],
            out_specs=pl.BlockSpec((1, gq, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((gq, d), jnp.float32),
                pltpu.VMEM((gq, LANES), jnp.float32),
                pltpu.VMEM((gq, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, gq, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, seq_lens, q, k_pages_i8, k_scale[..., None], v_pages_i8, v_scale[..., None])


@functools.partial(
    jax.jit,
    static_argnames=("hq_per_kv", "scale", "bq", "bkv", "interpret"),
)
def decode_attention_pallas(
    q: jax.Array,  # (B*Hq, bq, D) — bq = padded single-token rows
    k_i8: jax.Array,  # (B*Hkv, S, D) int8
    k_scale: jax.Array,  # (B*Hkv, S) f32
    v_i8: jax.Array,
    v_scale: jax.Array,
    kv_valid_len: jax.Array,  # (1,) int32
    *,
    hq_per_kv: int,
    scale: Optional[float] = None,
    bq: int = 8,
    bkv: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    bh, bq_, d = q.shape
    skv = k_i8.shape[1]
    assert bq_ == bq
    scale = scale if scale is not None else 1.0 / (d**0.5)
    (nk,) = grid_for((skv,), (bkv,))
    grid = (bh, nk)

    # index maps receive the scalar-prefetch ref as a trailing argument
    q_map = lambda h, k_, len_ref: (h, 0, 0)
    kv_map = lambda h, k_, len_ref: (h // hq_per_kv, k_, 0)
    s_map = lambda h, k_, len_ref: (h // hq_per_kv, k_, 0)

    kernel = functools.partial(_kernel, nk=nk, bq=bq, bkv=bkv, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # kv_valid_len rides ahead of the DMAs
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bkv, d), kv_map),
                pl.BlockSpec((1, bkv, 1), s_map),
                pl.BlockSpec((1, bkv, d), kv_map),
                pl.BlockSpec((1, bkv, 1), s_map),
            ],
            out_specs=pl.BlockSpec((1, bq, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, bq, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_valid_len, q, k_i8, k_scale[..., None], v_i8, v_scale[..., None])
