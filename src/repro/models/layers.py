"""Model building blocks — pure JAX, fault-aware, shard-annotated.

Every parameterized matmul routes through ``fault_linear``/``fault_einsum``
so a chip's fault map (FaultContext) masks exactly the weights that the
systolic mapping places on faulty PEs (DESIGN.md S2).

Attention has three interchangeable implementations:
  dense      — materializes scores; for short q (decode) and tiny smoke tests
  blockwise  — pure-JAX flash (scan over q chunks, online softmax over kv
               chunks); memory-safe at 32k+, lowers on any backend
  pallas     — the TPU kernel (repro.kernels.flash_attention)
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.masking import FaultContext, fault_einsum, fault_linear
from repro.launch.sharding import shard_activation

Array = jax.Array


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x: Array, p: dict, eps: float) -> Array:
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], eps)
    return rms_norm(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, H, S, D); positions: (B, S) absolute token positions."""
    d = x.shape[-1]
    inv = rope_frequencies(d, theta)  # (D/2,)
    ang = positions[:, None, :, None].astype(jnp.float32) * inv  # (B,1,S,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention implementations
# ---------------------------------------------------------------------------


def dense_attention(
    q: Array, k: Array, v: Array, *, causal: bool, window: Optional[int],
    q_offset, kv_valid_len=None, scale: Optional[float] = None,
    segments: Optional[Array] = None,
) -> Array:
    """Materializing attention; q_offset may be a traced scalar (decode).

    ``q_offset`` / ``kv_valid_len`` may also be per-sequence ``(B,)`` arrays
    (the continuous-batching decode path, where every slot sits at its own
    position in its own KV chain); the scalar path is left byte-identical.

    ``segments`` is a ``(B, S)`` int array for packed prefill (several
    prompts in one row, ``repro.serve.bucketing``): tokens may only attend
    within their own segment. Requires ``sq == skv`` — the ids describe
    queries and keys at once. Causal/window masks stay in packed-row index
    space, which equals per-segment position space within a segment because
    packed positions restart per segment.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s *= scale
    off = jnp.asarray(q_offset)
    vld = None if kv_valid_len is None else jnp.asarray(kv_valid_len)
    if segments is not None and sq != skv:
        raise ValueError(f"segment masking needs sq == skv, got {sq} vs {skv}")
    if off.ndim or (vld is not None and vld.ndim) or segments is not None:
        # per-sequence offsets/lengths: mask is (B, sq, skv)
        rows = jnp.broadcast_to(off, (b,))[:, None, None] + jnp.arange(sq)[None, :, None]
        cols = jnp.arange(skv)[None, None, :]
        mask = jnp.ones((b, sq, skv), dtype=bool)
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        if vld is not None:
            mask = mask & (cols < jnp.broadcast_to(vld, (b,))[:, None, None])
        if segments is not None:
            mask = mask & (segments[:, :, None] == segments[:, None, :])
        s = jnp.where(mask[:, None, None], s, -1e30)
    else:
        rows = jnp.arange(sq)[:, None] + q_offset
        cols = jnp.arange(skv)[None, :]
        mask = jnp.ones((sq, skv), dtype=bool)
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        if kv_valid_len is not None:
            mask = mask & (cols < kv_valid_len)
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def blockwise_attention(
    q: Array, k: Array, v: Array, *, causal: bool, window: Optional[int],
    q_offset: int = 0, q_chunk: int = 1024, kv_chunk: int = 1024,
    scale: Optional[float] = None, mixed: bool = False, unroll: bool = False,
) -> Array:
    """Pure-JAX flash attention: O(S * w) for sliding windows via dynamic
    kv slices, online softmax over kv chunks otherwise. Lowers on all
    backends with flat memory; the HLO is a 2-level scan.

    mixed=True keeps the QK/PV dots in the input dtype with fp32
    accumulation (halves score-buffer traffic; softmax stats stay fp32).
    unroll=True unrolls the causal q-chunk loop with STATIC per-chunk kv
    extents, eliminating the 2x fully-masked-block waste of the scan form.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = hq // hkv
    q_chunk = min(q_chunk, sq)
    while sq % q_chunk:
        q_chunk //= 2
    kv_chunk = min(kv_chunk, skv)
    while skv % kv_chunk:
        kv_chunk //= 2
    nq = sq // q_chunk

    dot_dtype = q.dtype if mixed else jnp.float32
    kg = k.astype(dot_dtype)
    vg = v.astype(dot_dtype)

    if unroll and causal and window is None and q_offset == 0 and sq == skv:
        # static causal extents: chunk qi attends kv[0 : (qi+1)*q_chunk]
        outs = []
        for qi in range(nq):
            qs = qi * q_chunk
            qc = q[:, :, qs : qs + q_chunk].astype(dot_dtype)
            qcg = qc.reshape(b, hkv, group, q_chunk, d)
            kc = kg[:, :, : qs + q_chunk]
            vc = vg[:, :, : qs + q_chunk]
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qcg, kc, preferred_element_type=jnp.float32
            ) * scale
            rows = qs + jnp.arange(q_chunk)[:, None]
            cols = jnp.arange(qs + q_chunk)[None, :]
            s = jnp.where((cols <= rows)[None, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(dot_dtype), vc,
                preferred_element_type=jnp.float32,
            )
            outs.append(o.reshape(b, hq, q_chunk, d).astype(q.dtype))
        return jnp.concatenate(outs, axis=2)

    if window is not None:
        # SWA: each q chunk only needs kv span [qs - window + 1, qs + q_chunk)
        span = window + q_chunk
        span = min(span, skv)

        def one_chunk(qi):
            qs = qi * q_chunk
            qc = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=2).astype(dot_dtype)
            start = jnp.clip(qs + q_offset - window + 1, 0, skv - span)
            kc = jax.lax.dynamic_slice_in_dim(kg, start, span, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vg, start, span, axis=2)
            qcg = qc.reshape(b, hkv, group, q_chunk, d)
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qcg, kc, preferred_element_type=jnp.float32
            ) * scale
            rows = qs + q_offset + jnp.arange(q_chunk)[:, None]
            cols = start + jnp.arange(span)[None, :]
            m = (cols <= rows) if causal else jnp.ones_like(cols <= rows)
            m = m & (cols > rows - window)
            s = jnp.where(m[None, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(dot_dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return o.reshape(b, hq, q_chunk, d).astype(q.dtype)

        chunks = jax.lax.map(one_chunk, jnp.arange(nq))
        return jnp.moveaxis(chunks, 0, 2).reshape(b, hq, sq, d)

    nk = skv // kv_chunk

    def one_q_chunk(qi):
        qs = qi * q_chunk
        qc = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=2).astype(dot_dtype)
        qcg = qc.reshape(b, hkv, group, q_chunk, d)

        def inner(carry, ki):
            acc, m_run, l_run = carry
            ks = ki * kv_chunk
            kc = jax.lax.dynamic_slice_in_dim(kg, ks, kv_chunk, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vg, ks, kv_chunk, axis=2)
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qcg, kc, preferred_element_type=jnp.float32
            ) * scale
            if causal:
                rows = qs + q_offset + jnp.arange(q_chunk)[:, None]
                cols = ks + jnp.arange(kv_chunk)[None, :]
                s = jnp.where((cols <= rows)[None, None, None], s, -1e30)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_run, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha[..., 0][..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(dot_dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, hkv, group, q_chunk, d), jnp.float32)
        m0 = jnp.full((b, hkv, group, q_chunk, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, group, q_chunk, 1), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(inner, (acc0, m0, l0), jnp.arange(nk))
        o = acc / jnp.maximum(l_run[..., 0][..., None], 1e-30)
        return o.reshape(b, hq, q_chunk, d).astype(q.dtype)

    chunks = jax.lax.map(one_q_chunk, jnp.arange(nq))
    return jnp.moveaxis(chunks, 0, 2).reshape(b, hq, sq, d)


def attention_impl(
    q, k, v, *, causal, window, q_offset=0, impl="auto", kv_valid_len=None, scale=None,
    segments=None,
):
    sq = q.shape[2]
    if impl == "auto":
        impl = (
            "dense"
            if (sq <= 512 or kv_valid_len is not None or segments is not None)
            else "blockwise"
        )
    if impl == "dense":
        return dense_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            kv_valid_len=kv_valid_len, scale=scale, segments=segments,
        )
    if segments is not None:
        raise ValueError(f"segment-packed attention is dense-only, got impl {impl!r}")
    if impl.startswith("blockwise"):
        return blockwise_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale,
            mixed="_mx" in impl, unroll="_unroll" in impl,
        )
    if impl == "pallas":
        from repro.kernels.flash_attention.ops import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=window, q_offset=int(q_offset), scale=scale
        )
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------------------
# Attention block (GQA + RoPE + qk_norm + SWA) with optional KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: Array  # (B, Hkv, S_buf, D)
    v: Array
    index: Array  # scalar int32: absolute position of next token


class PagedKVView(NamedTuple):
    """One layer's slice of a paged KV cache (repro.serve.kvcache).

    The pool holds ``P`` pages of ``page_size`` tokens each; slot ``b``'s
    history is the page chain ``block_tables[b]`` truncated to
    ``seq_lens[b]`` tokens. Page 0 is reserved as a scratch page: writes of
    masked-out slots (``write_mask`` False — retired slots between
    retirement and re-admission) are redirected there so they can never
    corrupt pages the allocator has already handed to another slot.
    """

    k_pages: Array  # (P, Hkv, page_size, D)
    v_pages: Array
    block_tables: Array  # (S, max_pages) int32 page ids
    seq_lens: Array  # (S,) int32 tokens already cached per slot
    write_mask: Optional[Array]  # (S,) bool; None = every slot writes


def attention_block(
    p: dict,
    x: Array,  # (B, S, d_model)
    cfg,
    ctx: FaultContext,
    *,
    positions: Array,
    impl: str = "auto",
    cache: Optional[KVCache] = None,
    return_kv: bool = False,
    segments: Optional[Array] = None,
):
    """Returns (out, new_cache). With ``return_kv`` (prefill) the second
    element is the raw (k, v) pair (B, Hkv, S, D) for cache assembly.
    ``segments`` (packed prefill, cache-free path only) restricts attention
    to same-segment tokens — see ``dense_attention``."""
    b, s, _ = x.shape
    if segments is not None and cache is not None:
        raise ValueError("segment-packed attention is a cache-free prefill path")
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = fault_linear(x, p["wq"], ctx).reshape(b, s, hq, hd)
    k = fault_linear(x, p["wk"], ctx).reshape(b, s, hkv, hd)
    v = fault_linear(x, p["wv"], ctx).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = jnp.moveaxis(q, 1, 2)  # (B, H, S, D)
    k = jnp.moveaxis(k, 1, 2)
    v = jnp.moveaxis(v, 1, 2)
    if cfg.use_rope and not cfg.is_encoder:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_activation(q, ("batch", "heads", "seq", None))

    new_cache = None
    if isinstance(cache, PagedKVView):
        # paged decode: scatter the new token into each slot's current page,
        # gather the slot's page chain, attend with per-slot positions
        if s != 1:
            raise ValueError(f"paged decode is one token per step, got s={s}")
        page = cache.k_pages.shape[2]
        maxp = cache.block_tables.shape[1]
        pos = cache.seq_lens  # (S,)
        chain_ix = jnp.clip(pos // page, 0, maxp - 1)
        page_ix = jnp.take_along_axis(cache.block_tables, chain_ix[:, None], axis=1)[:, 0]
        if cache.write_mask is not None:
            page_ix = jnp.where(cache.write_mask, page_ix, 0)  # page 0 = scratch
        off = pos % page
        k_pages = cache.k_pages.at[page_ix, :, off].set(k[:, :, 0].astype(cache.k_pages.dtype))
        v_pages = cache.v_pages.at[page_ix, :, off].set(v[:, :, 0].astype(cache.v_pages.dtype))
        kg = jnp.moveaxis(jnp.take(k_pages, cache.block_tables, axis=0), 2, 1)
        vg = jnp.moveaxis(jnp.take(v_pages, cache.block_tables, axis=0), 2, 1)
        kg = kg.reshape(b, hkv, maxp * page, hd)  # (S, Hkv, maxp*page, D)
        vg = vg.reshape(b, hkv, maxp * page, hd)
        o = dense_attention(
            q, kg, vg, causal=True, window=cfg.sliding_window,
            q_offset=pos, kv_valid_len=pos + 1, scale=None,
        )
        new_cache = PagedKVView(
            k_pages, v_pages, cache.block_tables, cache.seq_lens, cache.write_mask
        )
    elif cache is not None:
        s_buf = cache.k.shape[2]
        window = cfg.sliding_window
        # rolling buffer for SWA; linear buffer otherwise
        slot = cache.index % s_buf if (window and s_buf == window) else cache.index
        k_new = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), slot, axis=2)
        v_new = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), slot, axis=2)
        new_cache = KVCache(k_new, v_new, cache.index + s)
        if window and s_buf == window:
            # ring buffer: re-order not needed — attend to all valid slots
            valid = jnp.minimum(cache.index + s, s_buf)
            o = dense_attention(
                q, k_new, v_new, causal=False, window=None,
                q_offset=0, kv_valid_len=valid, scale=None,
            )
        else:
            o = dense_attention(
                q, k_new, v_new, causal=True, window=window,
                q_offset=cache.index, kv_valid_len=cache.index + s, scale=None,
            )
    else:
        o = attention_impl(
            q, k, v, causal=not cfg.is_encoder, window=cfg.sliding_window,
            q_offset=0, impl=impl, segments=segments,
        )
        if return_kv:
            new_cache = (k, v)
    o = jnp.moveaxis(o, 1, 2).reshape(b, s, hq * hd)
    out = fault_linear(o, p["wo"], ctx)
    # steer the partitioner to reduce-scatter (not all-reduce + slice) the
    # TP partial sums straight into the carry layout
    out = shard_activation(out, ("batch", "seq_carry", "embed"))
    return out, new_cache


# NOTE on the SWA ring buffer: attention over the ring ignores token order
# because softmax is permutation-invariant given correct masking; with a
# full ring every slot is a valid in-window key. RoPE is applied before
# caching, so positional geometry is preserved. During the first ``window``
# tokens the kv_valid_len mask hides unwritten slots.


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_block(p: dict, x: Array, cfg, ctx: FaultContext) -> Array:
    if cfg.activation == "swiglu":
        g = fault_linear(x, p["wg"], ctx)
        u = fault_linear(x, p["wu"], ctx)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(fault_linear(x, p["wi"], ctx))
    h = shard_activation(h, ("batch", None, "mlp"))
    out = fault_linear(h, p["wd"], ctx)
    return shard_activation(out, ("batch", "seq_carry", "embed"))
