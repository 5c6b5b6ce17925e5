"""Paged KV cache: page-pool layout, free-list allocation, byte accounting.

The dense serving cache allocates ``max_len`` KV slots per sequence up
front and holds them until the whole batch finishes. The paged layout
replaces that with a shared pool of fixed-size pages:

* the **pool** (``repro.models.model.init_paged_cache``) is a
  ``(L, num_pages, Hkv, page_size, hd)`` pair of zero-initialized arrays;
* each slot owns a **page chain** — a row of ``block_tables`` holding the
  page ids of its history in order, truncated to ``seq_lens[slot]`` tokens;
* the **allocator** (host-side, this module) hands page ids out of a free
  list at admission and takes them back at retirement, so a finished
  request's memory is reusable immediately, mid-flight.

Page 0 is *reserved*: it is never allocated, and the device-side write path
(``repro.models.layers.PagedKVView``) redirects masked-out slots' writes to
it, so a retired slot can never corrupt a page that has already been handed
to another request.

The device-side read path is a gather (``jnp.take`` over the pool by block
table) feeding per-slot masked dense attention — wired into
``models/model.py::decode_step``; the quantized TPU analog is
``repro.kernels.decode_attention.ops.paged_decode_attention``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "DEFAULT_PAGE_SIZE",
    "PageAllocator",
    "pages_needed",
    "round_up_to_page",
    "chain_layout",
    "dense_kv_bytes",
    "page_bytes",
    "ssm_state_bytes",
]

DEFAULT_PAGE_SIZE = 8


def pages_needed(num_tokens: int, page_size: int) -> int:
    """Pages required to hold ``num_tokens`` KV entries."""
    return -(-int(num_tokens) // int(page_size))


def round_up_to_page(num_tokens: int, page_size: int) -> int:
    return pages_needed(num_tokens, page_size) * int(page_size)


@dataclass
class PageAllocator:
    """Host-side free-list allocator over a pool of ``num_pages`` pages.

    Page 0 is reserved as the scratch page for masked writes and is never
    handed out. Allocation is LIFO over the free list (freed pages are
    reused first — the pool stays compact); ``peak_pages`` tracks the
    high-water mark for resident-bytes accounting.

    The allocator tracks exactly which pages are outstanding (``_in_use``):
    freeing a page it never handed out — a double free OR a "foreign" free
    of a page owned by another chain, which the old in-free-list check
    could not see — raises instead of silently corrupting the free list
    with a page some other request is still writing.

    Observability counters consumed by :class:`repro.obs.hooks.PoolMonitor`:
    ``high_water`` (peak pages in use) and ``alloc_failures`` — the number
    of times an allocation was refused for lack of pages, counting both a
    failed :meth:`alloc` and a ``False`` answer from :meth:`can_alloc`
    (the admission loops probe ``can_alloc`` before committing, so each
    refusal is one backpressure stall).
    """

    num_pages: int
    page_size: int
    _free: list = field(default_factory=list)
    _in_use: set = field(default_factory=set)
    peak_pages: int = 0
    alloc_failures: int = 0

    def __post_init__(self):
        if self.num_pages < 2:
            raise ValueError(f"need >= 2 pages (page 0 is reserved), got {self.num_pages}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        # descending so pop() hands out low page ids first (stable tests)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._in_use = set()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def high_water(self) -> int:
        """Peak pages in use over the allocator's lifetime."""
        return self.peak_pages

    def can_alloc(self, n: int) -> bool:
        ok = n <= len(self._free)
        if not ok:
            self.alloc_failures += 1
        return ok

    def alloc(self, n: int) -> list[int]:
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if n > len(self._free):
            self.alloc_failures += 1
            raise MemoryError(
                f"page pool exhausted: need {n} pages, {len(self._free)} free "
                f"of {self.num_pages - 1} allocatable"
            )
        out = [self._free.pop() for _ in range(n)]
        self._in_use.update(out)
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        return out

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            p = int(p)
            if p in self._in_use:
                self._in_use.discard(p)
                self._free.append(p)
                continue
            if 0 < p < self.num_pages and p in self._free:
                raise ValueError(f"double free of page {p}")
            raise ValueError(
                f"free of page {p} this allocator never handed out "
                "(foreign page — reserved, outside the pool, or another "
                "allocator owns it)"
            )


def chain_layout(k_dense: jax.Array, page_size: int, chain_len: int) -> jax.Array:
    """Re-layout one sequence's dense KV ``(L, 1, Hkv, plen, hd)`` into page
    chain form ``(L, chain_len, Hkv, page_size, hd)`` for a one-shot scatter
    into the pool (``pool.at[:, page_ids].set(...)``). The tail page is
    zero-padded past ``plen``."""
    L, b, hkv, plen, hd = k_dense.shape
    if b != 1:
        raise ValueError(f"chain_layout takes one sequence, got batch {b}")
    total = chain_len * page_size
    if plen > total:
        raise ValueError(f"{plen} tokens exceed chain capacity {total}")
    k = jnp.pad(k_dense[:, 0], ((0, 0), (0, 0), (0, total - plen), (0, 0)))
    k = k.reshape(L, hkv, chain_len, page_size, hd)
    return jnp.moveaxis(k, 1, 2)  # (L, chain, Hkv, page, hd)


def _kv_entry_bytes(cfg) -> int:
    """Bytes of one token's K+V across the attention layers."""
    return 2 * cfg.num_attn_layers * cfg.num_kv_heads * cfg.resolved_head_dim * jnp.dtype(cfg.dtype).itemsize


def page_bytes(cfg, page_size: int) -> int:
    """Resident bytes of ONE page (K+V, all attention layers)."""
    return _kv_entry_bytes(cfg) * int(page_size)


def ssm_state_bytes(cfg, num_slots: int) -> int:
    """Resident bytes of the recurrent state ``num_slots`` slots hold beside
    their page chains: each SSM layer's float32 conv-input tail and SSM
    state (``models/model.py::init_paged_cache``)."""
    per_layer = cfg.d_inner * (cfg.ssm_conv - 1 + cfg.ssm_state) * 4
    return cfg.num_ssm_layers * per_layer * int(num_slots)


def dense_kv_bytes(cfg, batch: int, cache_len: int) -> int:
    """Resident bytes of a dense ``init_cache(cfg, batch, cache_len)``
    (window-bounded for SWA, mirroring ``model.cache_buffer_len``)."""
    buf = min(cfg.sliding_window, cache_len) if cfg.sliding_window else cache_len
    return _kv_entry_bytes(cfg) * int(batch) * int(buf)
