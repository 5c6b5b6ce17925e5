"""Continuous-batching serving engine over the paged KV cache.

The static engine (``repro.serve.engine.ServeEngine``) runs one rectangular
prompt batch to the longest request's horizon: a request that finishes at
token 5 burns a dispatch per token until the batch's longest request
finishes, and every sequence owns a dense ``max_len`` KV buffer for the
whole run. This module replaces that with the standard serving loop:

* a **request queue** of :class:`Request`\\ s (own prompt, own
  ``max_new_tokens``, own arrival step);
* a **slot table** of ``num_slots`` decode lanes; requests admit into free
  slots (prefill on arrival), retire on EOS or their own budget, and free
  their pages immediately so a waiting request refills the slot mid-flight;
* ONE fused jitted decode step for the whole slot table — the masked form
  of ``make_sample_decode`` (per-slot ``active`` masking, per-slot
  ``remaining`` budgets) over the paged cache from
  ``models/model.py::decode_step``.

Admission runs over a CLOSED set of prefill shapes (``repro.serve.
bucketing``): prompts pad up to a small bucket ladder, several short
waiting prompts pack into one bucket dispatch as segment-masked rows of a
single packed sequence, and prompts longer than the top bucket stream into
their page chain in fixed-size chunks (``models/model.py::prefill_chunk``)
— so total prefill compile volume is O(|buckets|), independent of the
traffic's prompt-length mix, and :meth:`ContinuousBatchingEngine.warmup`
AOT-compiles every shape (``jit(...).lower().compile()``) before traffic
arrives. The static analyzer's recompile census
(``repro.analysis.recompile``) models exactly this signature set.

Decode math per request is the same prefill + masked-attention math the
static engine runs, so greedy outputs are pinned token-for-token against
``ServeEngine`` on the same prompt with the same budget — including
requests admitted mid-flight and packed/chunked admissions
(tests/test_serve_continuous.py).

Host/device split: sampling, masking and the paged read/write all live in
the jitted steps; the host loop only moves tiny per-slot flags (emitted
tokens, the active mask) to run admission/retirement between dispatches,
plus the int32 pack/chunk index maps built by ``repro.serve.bucketing``.

An interleaved stack (Jamba: Mamba layers between attention layers) keeps
each slot's recurrent state — every Mamba layer's conv-input tail and SSM
state — beside its page chain, in the paged cache; the pool covers the
attention layers only. A prompt's first prefill dispatch starts the state
from zero, and each chunk continues from the one before it. Its prompts
are never packed: one scan runs along a packed row, so a second prompt
would start from the first one's state.

A serving chip's weights are frozen, so on a faulty chip the fault mask is
applied once, not per use: at build (and on :meth:`set_silicon`) one small
jitted program masks every array-mapped GEMM weight, and a tied model's
``embed.T`` into an ``lm_head`` beside the raw ``embed`` the lookup reads.
The serving programs then run on those weights with a context that masks
nothing, so none of them holds a mask op.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mapping import masked_weight
from repro.core.masking import MASK_SCOPE, FaultContext, healthy, is_array_mapped
from repro.launch.hlo_cost import module_name, scoped_instructions
from repro.models import model as M
from repro.models.ssm import MAMBA_SCOPE, SCAN_SCOPE
from repro.obs.alerts import AlertEngine, AlertRule
from repro.obs.health import HealthConfig, HealthTracker
from repro.obs.hooks import PoolMonitor, RequestTracer
from repro.obs.recorder import NULL_RECORDER, Recorder
from repro.serve.bucketing import (
    DEFAULT_PREFILL_BUCKETS,
    PackItem,
    bucket_of,
    build_pack,
    chunk_step_maps,
    plan_prefill,
    validate_buckets,
)
from repro.serve.engine import make_sample_decode
from repro.serve.kvcache import (
    DEFAULT_PAGE_SIZE,
    PageAllocator,
    page_bytes,
    pages_needed,
    ssm_state_bytes,
)

__all__ = [
    "Request",
    "RequestOutput",
    "ServeStats",
    "ContinuousBatchingEngine",
    "shape_structs",
]


def shape_structs(tree):
    """ShapeDtypeStruct mirror of a pytree — AOT lowering without arrays."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), tree
    )


@dataclass(frozen=True)
class Request:
    """One generation request in a stream.

    ``arrival`` is the decode-dispatch index at (or after) which the request
    may be admitted — 0 means it is waiting before serving starts."""

    rid: int
    tokens: np.ndarray  # (prompt_len,) int token ids
    max_new_tokens: int
    arrival: int = 0

    def __post_init__(self):
        object.__setattr__(self, "tokens", np.asarray(self.tokens))
        if self.tokens.ndim != 1 or self.tokens.shape[0] < 1:
            raise ValueError(f"request {self.rid}: prompt must be a non-empty 1-D array")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens must be >= 1")


@dataclass
class RequestOutput:
    rid: int
    prompt: np.ndarray
    tokens: np.ndarray  # (generated,) — includes the EOS token if hit
    logprobs: np.ndarray
    admitted_step: int  # dispatch index at admission (prefill time)
    finished_step: int  # dispatch index after the final token
    finish_reason: str  # "eos" | "length"
    queue_wait_steps: int = 0  # admitted_step - arrival (admission backpressure)
    ttft_wall_s: float = float("nan")  # arrival seen -> first token, wall clock

    @property
    def ttft(self) -> int:
        """Decode dispatches from serve start until this request's first
        token (its prefill emits no token; the next dispatch does)."""
        return self.admitted_step + 1


@dataclass
class ServeStats:
    decode_dispatches: int = 0
    prefill_dispatches: int = 0  # packed-bucket + chunk dispatches
    chunk_dispatches: int = 0  # chunked-prefill subset of the above
    probe_dispatches: int = 0  # ABFT canary/structured probe GEMMs
    emitted_tokens: int = 0
    admitted: int = 0
    num_slots: int = 0
    page_size: int = 0
    active_slot_steps: int = 0  # sum over dispatches of active slots
    peak_resident_kv_bytes: int = 0
    kv_byte_steps: int = 0  # sum over dispatches of resident kv bytes

    @property
    def slot_utilization(self) -> float:
        if not self.decode_dispatches:
            return 0.0
        return self.active_slot_steps / (self.decode_dispatches * self.num_slots)

    def as_dict(self) -> dict:
        return dict(
            decode_dispatches=self.decode_dispatches,
            prefill_dispatches=self.prefill_dispatches,
            chunk_dispatches=self.chunk_dispatches,
            probe_dispatches=self.probe_dispatches,
            emitted_tokens=self.emitted_tokens,
            admitted=self.admitted,
            num_slots=self.num_slots,
            page_size=self.page_size,
            slot_utilization=self.slot_utilization,
            peak_resident_kv_bytes=self.peak_resident_kv_bytes,
            kv_byte_steps=self.kv_byte_steps,
        )


class _SlotTable:
    """Host-side slot bookkeeping for one chip's continuous-batch state.

    Owns the page allocator, the pending queue (arrival order, stable), the
    per-slot request records and the accumulating outputs. The device-side
    arrays live with the engine; this class only decides who sits where."""

    def __init__(self, requests: Sequence[Request], num_slots: int, allocator: PageAllocator,
                 max_pages_per_seq: int):
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError(f"duplicate request ids in stream: {sorted(rids)}")
        self.pending: list[Request] = sorted(
            requests, key=lambda r: (r.arrival, r.rid)
        )
        self.alloc = allocator
        self.max_pages_per_seq = max_pages_per_seq
        self.slots: list[Optional[Request]] = [None] * num_slots
        self.slot_pages: list[list[int]] = [[] for _ in range(num_slots)]
        self.active = np.zeros(num_slots, bool)
        self.outputs: dict[int, RequestOutput] = {}
        self.outputs_admitted: dict[int, int] = {}  # rid -> admission clock
        self._tok: dict[int, list] = {}
        self._lp: dict[int, list] = {}
        self._arrival_wall: dict[int, float] = {}  # rid -> wall time first eligible
        self._first_tok_wall: dict[int, float] = {}
        for r in self.pending:
            need = pages_needed(len(r.tokens) + r.max_new_tokens, allocator.page_size)
            if need > max_pages_per_seq:
                raise ValueError(
                    f"request {r.rid} needs {need} pages "
                    f"(prompt {len(r.tokens)} + budget {r.max_new_tokens}) but "
                    f"max_pages_per_seq={max_pages_per_seq}"
                )

    @property
    def done(self) -> bool:
        return not self.pending and not self.active.any()

    def next_arrival(self) -> Optional[int]:
        return self.pending[0].arrival if self.pending else None

    def stamp_arrivals(self, clock: int) -> None:
        """Record the wall time each pending request first became eligible
        (its arrival clock was reached) — the start of its queue wait."""
        now = time.perf_counter()
        for r in self.pending:
            if r.arrival > clock:
                break  # pending is arrival-sorted
            self._arrival_wall.setdefault(r.rid, now)

    def pop_admission(self, clock: int) -> Optional[tuple[int, Request, list[int]]]:
        """Admit the next arrived request into a free slot, allocating its
        full page chain. None when no slot/request/pages are available."""
        if not self.pending or self.pending[0].arrival > clock:
            return None
        free = [s for s, r in enumerate(self.slots) if r is None]
        if not free:
            return None
        r = self.pending[0]
        need = pages_needed(len(r.tokens) + r.max_new_tokens, self.alloc.page_size)
        if not self.alloc.can_alloc(need):
            if not self.active.any():
                raise MemoryError(
                    f"request {r.rid} needs {need} pages but only "
                    f"{self.alloc.free_pages} are free and no request is in "
                    "flight to retire — grow num_pages"
                )
            return None  # wait for a retirement to free pages
        self.pending.pop(0)
        slot = free[0]
        pages = self.alloc.alloc(need)
        self.slots[slot] = r
        self.slot_pages[slot] = pages
        self.active[slot] = True
        self._tok[r.rid] = []
        self._lp[r.rid] = []
        return slot, r, pages

    def record_step(
        self,
        emitted: np.ndarray,
        lps: np.ndarray,
        new_active: np.ndarray,
        clock: int,
        eos_id: Optional[int] = None,
    ) -> list[int]:
        """Record one dispatch's per-slot emissions; retire newly-finished
        slots (freeing their pages). Returns the retired rids."""
        retired = []
        now = time.perf_counter()
        for s, r in enumerate(self.slots):
            if r is None or not self.active[s]:
                continue
            self._tok[r.rid].append(int(emitted[s]))
            self._lp[r.rid].append(float(lps[s]))
            if len(self._tok[r.rid]) == 1:
                self._first_tok_wall[r.rid] = now
            if not new_active[s]:
                toks = np.asarray(self._tok.pop(r.rid))
                # the EOS check wins even on the last budgeted token — it is
                # what actually cleared the slot's mask on the device
                reason = (
                    "eos"
                    if eos_id is not None and toks.size and toks[-1] == eos_id
                    else "length"
                )
                admitted = self.outputs_admitted[r.rid]
                t0 = self._arrival_wall.get(r.rid)
                t1 = self._first_tok_wall.get(r.rid)
                self.outputs[r.rid] = RequestOutput(
                    rid=r.rid,
                    prompt=np.asarray(r.tokens),
                    tokens=toks,
                    logprobs=np.asarray(self._lp.pop(r.rid)),
                    admitted_step=admitted,
                    finished_step=clock,
                    finish_reason=reason,
                    queue_wait_steps=admitted - r.arrival,
                    ttft_wall_s=(t1 - t0) if t0 is not None and t1 is not None else float("nan"),
                )
                self.alloc.free(self.slot_pages[s])
                self.slot_pages[s] = []
                self.slots[s] = None
                retired.append(r.rid)
        self.active = np.array(new_active, bool) & np.array(
            [r is not None for r in self.slots]
        )
        return retired


class ContinuousBatchingEngine:
    """Continuous batching on one chip: paged KV + slot table + one fused
    masked decode step per token across all in-flight requests, admitted
    through the bucketed/packed/chunked planner (``repro.serve.bucketing``).

    ``prefill_buckets=None`` disables the planner (one exact-length
    admission program per distinct prompt length — the unbucketed baseline
    ``benchmarks/serve_bench.py --heavy-traffic`` measures against). A
    model with SSM layers packs one prompt per admission (``max_pack`` 1).
    """

    def __init__(
        self,
        cfg,
        params,
        ctx: Optional[FaultContext] = None,
        *,
        num_slots: int = 4,
        page_size: int = DEFAULT_PAGE_SIZE,
        num_pages: int = 128,
        max_pages_per_seq: Optional[int] = None,
        pad_id: int = 0,
        prefill_buckets: Optional[Sequence[int]] = DEFAULT_PREFILL_BUCKETS,
        chunk_size: Optional[int] = None,
        max_pack: int = 4,
        recorder: Optional[Recorder] = None,
        probe_every: Optional[int] = None,
        health_config: Optional[HealthConfig] = None,
        alert_rules: Optional[Sequence[AlertRule]] = None,
    ):
        M.check_pageable(cfg, "continuous batching")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.cfg = cfg
        # the weights as given: the prober's and every premask's source
        self.params = params
        # the LIVE fault context: set_silicon's checks and the prober read it
        self.ctx = ctx or healthy()
        self.num_slots = num_slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages_per_seq = max_pages_per_seq or (num_pages - 1)
        self.pad_id = pad_id
        # observability: every hook below is host-side and gated on the
        # recorder's truthiness, so an absent/disabled recorder costs one
        # check per dispatch and recording cannot touch traced code (greedy
        # parity with recorder on vs off is pinned in tests/test_obs.py)
        self.obs = recorder if recorder is not None else NULL_RECORDER
        self._page_bytes = page_bytes(cfg, page_size)
        if prefill_buckets is None:
            self.prefill_buckets = None
            self.chunk_size: Optional[int] = None
            self.max_pack = 1
        else:
            self.prefill_buckets = validate_buckets(prefill_buckets)
            self.chunk_size = int(chunk_size) if chunk_size else self.prefill_buckets[-1]
            if self.chunk_size < page_size or self.chunk_size % page_size:
                raise ValueError(
                    f"chunk_size {self.chunk_size} must be a positive multiple "
                    f"of page_size {page_size} (chunk starts must be page-aligned)"
                )
            if max_pack < 1:
                raise ValueError(f"max_pack must be >= 1, got {max_pack}")
            # one scan runs along a packed row: SSM state would carry over
            self.max_pack = 1 if cfg.has_ssm else int(max_pack)
        # every loop-carried operand (cur logits, paged cache, key, active
        # mask, remaining budgets) is re-bound from the previous dispatch's
        # outputs — donate them all so the page pool never round-trips
        # through a copy (repro.analysis DON001); params/ctx/eos and the
        # host-built pack/chunk index maps are reused or rebuilt per call
        # and stay undonated
        self._sample_decode = jax.jit(
            make_sample_decode(cfg, pad_id=pad_id), donate_argnums=(1, 2, 3, 6, 8)
        )
        self._packed_admit = jax.jit(
            self._packed_admit_fn, donate_argnums=(5, 6, 7, 8)
        )
        self._prefill_chunk = jax.jit(
            self._prefill_chunk_fn, donate_argnums=(3, 4, 5, 6)
        )
        # what the serving programs run on: on a faulty chip the premasked
        # weights and a context that masks nothing; on a healthy chip the
        # params as given
        self._serve_ctx = healthy()
        self._premask = jax.jit(self._premask_fn)
        self.served_params = self._premasked() if self.ctx.active else params
        # AOT-compiled executables by program key — see warmup(); dispatch
        # prefers these, falling back to the jit wrappers above (whose
        # _cache_size() then counts traffic-time compiles)
        self._aot: dict = {}
        self.used_programs: set = set()
        # HLO module name -> names of its ops under the fault-mask scope
        # (core/masking.py), from the AOT programs: empty lists, the mask
        # being applied at load; see warmup()
        self.mask_ops: dict[str, list[str]] = {}
        # ... and to its ops under the Mamba mixers' and scans' scopes
        # (models/ssm.py): both empty for a model without SSM layers
        self.mamba_ops: dict[str, list[str]] = {}
        self.scan_ops: dict[str, list[str]] = {}
        if cfg.has_ssm:
            self.obs.count("ssm.state_bytes", ssm_state_bytes(cfg, num_slots))
        # fault detection (ROADMAP item 2): an ABFT prober dispatched every
        # probe_every decode dispatches, feeding the health state machine
        # and the alert engine. Probes are SEPARATE dispatches through a
        # separate jitted program (outside compile_counts()/used_programs)
        # and never touch the serve loop's carried state or key stream, so
        # the PR-8 guarantee holds: enabling them changes no sampled token.
        if probe_every is not None and probe_every < 1:
            raise ValueError(f"probe_every must be >= 1, got {probe_every}")
        self.probe_every = int(probe_every) if probe_every else None
        self.prober = None
        self.health: Optional[HealthTracker] = None
        self.alerts = AlertEngine(self.obs, alert_rules) if alert_rules else None
        if self.probe_every:
            self._init_prober(health_config)

    def _init_prober(self, health_config: Optional[HealthConfig]) -> None:
        from repro.kernels.masked_matmul.ops import masked_matmul_checksummed
        from repro.obs.abft import ChipProber, select_probe_weight

        cfg = self.cfg
        rows, cols = cfg.array_rows, cfg.array_cols
        name, w = select_probe_weight(self.params)
        probe_fn = jax.jit(masked_matmul_checksummed)
        ones = jnp.ones((rows, cols), jnp.float32)
        dtype = jnp.dtype(cfg.dtype)

        def dispatch(x):
            # the LIVE mask: re-read self.ctx so a set_silicon() change is
            # what the next probe computes through (same shape, no recompile)
            ok = self.ctx.ok if self.ctx.ok is not None else ones
            y, chk = probe_fn(jnp.asarray(x, dtype), w, ok)
            return np.asarray(y), np.asarray(chk)

        self._probe_weight = name
        # snapshotting compiles the probe program and records goldens under
        # the believed map — before traffic, so probes never jit mid-serve
        self.prober = ChipProber(
            dispatch, array_shape=(rows, cols), k_dim=int(w.shape[0])
        )
        self.health = HealthTracker(
            1, self.obs, config=health_config, proc="serve"
        )

    def set_silicon(self, ctx: FaultContext) -> None:
        """Simulate a mid-flight silicon change: swap the LIVE fault context
        and re-mask the served weights from the raw ones under it (the same
        premask program at the same shapes, so nothing compiles), so every
        subsequent dispatch (decode, prefill, probes) computes through the
        new map, WITHOUT rebasing the prober's golden snapshots — so the
        next probe sees the divergence. The engine must have been built
        with an ACTIVE context of the same mask shape (a zero-fault
        ``FaultMap`` context models pristine silicon): a healthy engine
        serves the raw params, and its AOT executables were compiled for
        their pytree structure, which has no tied ``lm_head``."""
        cur = self.ctx
        if cur.ok is None or ctx is None or ctx.ok is None:
            raise ValueError(
                "set_silicon needs ACTIVE fault contexts on both sides; "
                "construct the engine with an explicit (possibly zero-fault)"
                " FaultMap context so its served weights are premasked"
            )
        if cur.mode != ctx.mode or tuple(cur.ok.shape) != tuple(ctx.ok.shape):
            raise ValueError(
                f"silicon change must keep mode/shape: have "
                f"{cur.mode}/{tuple(cur.ok.shape)}, "
                f"got {ctx.mode}/{tuple(ctx.ok.shape)}"
            )
        self.ctx = ctx
        self.served_params = self._premasked()

    # -- jitted pieces ------------------------------------------------------

    def _premask_fn(self, gemm, embed, ok):
        """The served weights under healthy-PE mask ``ok``: each GEMM weight
        times its periodic mask, in its own dtype, and — for a tied model —
        the masked ``embed.T`` that ``M.unembed`` reads as ``lm_head``."""
        with jax.named_scope(MASK_SCOPE):
            gemm = [masked_weight(w, ok) for w in gemm]
            return gemm, None if embed is None else masked_weight(embed.T, ok)

    def _premasked(self):
        """Run the premask program under the live context; the served params
        share every other leaf (the embedding the lookup reads, the norms)
        with the raw ones."""
        rec = self.obs
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        ix = [i for i, (path, w) in enumerate(flat) if is_array_mapped(path, w)]
        embed = self.params["embed"] if self.cfg.tie_embeddings else None
        with rec.timed("premask", proc="serve", track="host",
                       annotate=jax.profiler.TraceAnnotation):
            # one mask dtype, so a set_silicon map of another reuses the program
            gemm, head = self._premask(
                [flat[i][1] for i in ix], embed, jnp.asarray(self.ctx.ok, jnp.float32))
            jax.block_until_ready((gemm, head))
        rec.count("fault_mask.premask")
        leaves = [w for _, w in flat]
        for i, w in zip(ix, gemm):
            leaves[i] = w
        served = jax.tree_util.tree_unflatten(treedef, leaves)
        return served if head is None else {**served, "lm_head": head}

    def _packed_admit_fn(
        self, params, tokens, positions, segments, ctx, cache, cur, active,
        remaining, page_ix, page_off, gather_pos, slots, rows, seq_lens, budgets,
    ):
        """Admit a PACK of requests in one bucket-shaped dispatch: run the
        segment-masked prefill over the packed row, scatter every token's KV
        into its request's page chain (pad tokens hit the scratch page 0),
        gather each segment's last-token hidden state for its first logits,
        and splice per-slot state (unused pack lanes scatter out-of-bounds
        at ``slot == num_slots`` and are dropped). One compiled program per
        bucket, independent of pack occupancy and prompt lengths."""
        hidden, dense = M.prefill(
            params, {"tokens": tokens, "positions": positions}, self.cfg, ctx,
            full_kv=True, return_hidden=True, segments=segments, attn_impl="dense",
        )
        # (L, 1, Hkv, W, hd) -> (W, L, Hkv, hd): the advanced indices
        # (page_ix, page_off) around the Hkv slice put the token dim first
        k = jnp.transpose(dense["k"][:, 0], (2, 0, 1, 3))
        v = jnp.transpose(dense["v"][:, 0], (2, 0, 1, 3))
        kp = cache["k_pages"].at[:, page_ix, :, page_off].set(k.astype(cache["k_pages"].dtype))
        vp = cache["v_pages"].at[:, page_ix, :, page_off].set(v.astype(cache["v_pages"].dtype))
        h = hidden[0, gather_pos]  # (max_pack, d) — one last-token row per segment
        logits = M.unembed(self.cfg, params, h[None], ctx)[0]  # (max_pack, V)
        cache = dict(
            cache,
            k_pages=kp,
            v_pages=vp,
            block_tables=cache["block_tables"].at[slots].set(rows),
            seq_lens=cache["seq_lens"].at[slots].set(seq_lens),
        )
        for k in M.SSM_KEYS if self.cfg.has_ssm else ():
            # the row's one prompt: its state, from zero, replaces the slot's
            cache[k] = cache[k].at[:, slots].set(dense[k])
        cur = cur.at[slots].set(logits.astype(cur.dtype))
        active = active.at[slots].set(True)
        remaining = remaining.at[slots].set(budgets)
        return cache, cur, active, remaining

    def _prefill_chunk_fn(
        self, params, tokens, ctx, cache, cur, active, remaining,
        slot, row, page_ix, page_off, prefix, valid, budget, activate,
    ):
        """One chunk of a long prompt: continue against the slot's paged
        prefix (``models/model.py::prefill_chunk``), scatter the chunk's KV
        into the chain, and — on the final chunk (``activate``) — seed the
        slot's logits/budget and flip it live. Prefix/valid are traced, so
        every chunk of every prompt shares one compiled program. A model
        with SSM layers continues the slot's state, zero at the prompt's
        first chunk, and keeps the state after the chunk in the slot."""
        state = None
        if self.cfg.has_ssm:
            state = {k: jnp.where(prefix > 0, cache[k][:, slot], 0)[:, None]
                     for k in M.SSM_KEYS}
        logits, kc, vc, state = M.prefill_chunk(
            params, tokens, self.cfg, ctx,
            k_pages=cache["k_pages"], v_pages=cache["v_pages"], row=row,
            prefix_len=prefix, valid_len=valid, ssm_state=state,
        )
        k = jnp.transpose(kc[:, 0], (2, 0, 1, 3))
        v = jnp.transpose(vc[:, 0], (2, 0, 1, 3))
        new_len = jnp.where(activate, prefix + valid, cache["seq_lens"][slot])
        cache = dict(
            cache,
            **{k: cache[k].at[:, slot].set(v[:, 0]) for k, v in (state or {}).items()},
            k_pages=cache["k_pages"].at[:, page_ix, :, page_off].set(k.astype(cache["k_pages"].dtype)),
            v_pages=cache["v_pages"].at[:, page_ix, :, page_off].set(v.astype(cache["v_pages"].dtype)),
            block_tables=cache["block_tables"].at[slot].set(row),
            seq_lens=cache["seq_lens"].at[slot].set(new_len),
        )
        cur = cur.at[slot].set(jnp.where(activate, logits[0].astype(cur.dtype), cur[slot]))
        active = active.at[slot].set(active[slot] | activate)
        remaining = remaining.at[slot].set(jnp.where(activate, budget, remaining[slot]))
        return cache, cur, active, remaining

    # -- AOT warmup ---------------------------------------------------------

    def _state_structs(self):
        cfg = self.cfg
        cache = jax.eval_shape(
            lambda: M.init_paged_cache(cfg, self.num_pages, self.page_size,
                                       self.num_slots, self.max_pages_per_seq))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        cur = jax.ShapeDtypeStruct((self.num_slots, cfg.vocab_size), jnp.dtype(cfg.dtype))
        active = jax.ShapeDtypeStruct((self.num_slots,), jnp.bool_)
        remaining = i32(self.num_slots)
        return cache, cur, active, remaining

    def warmup(self) -> int:
        """AOT-precompile the closed program set before traffic arrives:
        one packed-admit program per bucket, the chunk program, and the
        fused decode step — ``jit(...).lower().compile()`` each, stored as
        executables the serve loop dispatches through directly. After
        warmup, traffic-time jit compiles (``compile_counts()``'s
        ``jit_fallback``) stay at zero. Also maps each program's HLO module
        name to its fault-mask ops (``mask_ops``), which ``serve()``
        publishes so that a device trace's mask time can be told apart
        (programs that share a module name, the bucket ladder's, share one
        list): none, on a healthy chip and on a premasked faulty one alike.
        The same maps of the ops under the Mamba mixers' and scans' scopes
        (``mamba_ops``, ``scan_ops``) are published beside it. Returns the
        AOT program count."""
        if self.prefill_buckets is None:
            raise ValueError("warmup() needs bucketed prefill; prefill_buckets is None")
        self._compile_programs()
        maps: dict = {MASK_SCOPE: {}, MAMBA_SCOPE: {}, SCAN_SCOPE: {}}
        for exe in self._aot.values():
            hlo = exe.as_text()
            for scope, ops in maps.items():
                ops.setdefault(module_name(hlo), set()).update(
                    scoped_instructions(hlo, scope))
        self.mask_ops, self.mamba_ops, self.scan_ops = (
            {m: sorted(ops) for m, ops in maps[scope].items()}
            for scope in (MASK_SCOPE, MAMBA_SCOPE, SCAN_SCOPE))
        return len(self._aot)

    def _compile_programs(self) -> None:
        params_s = shape_structs(self.served_params)
        ctx_s = shape_structs(self._serve_ctx)
        cache, cur, active, remaining = self._state_structs()
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        K, maxp = self.max_pack, self.max_pages_per_seq
        for w in self.prefill_buckets:
            key = ("prefill_admit", w)
            if key not in self._aot:
                self._aot[key] = self._packed_admit.lower(
                    params_s, i32(1, w), i32(1, w), i32(1, w), ctx_s,
                    cache, cur, active, remaining,
                    i32(w), i32(w), i32(K), i32(K), i32(K, maxp), i32(K), i32(K),
                ).compile()
        c = self.chunk_size
        key = ("prefill_chunk", c)
        if key not in self._aot:
            self._aot[key] = self._prefill_chunk.lower(
                params_s, i32(1, c), ctx_s, cache, cur, active, remaining,
                i32(), i32(maxp), i32(c), i32(c), i32(), i32(), i32(),
                jax.ShapeDtypeStruct((), jnp.bool_),
            ).compile()
        key = ("decode",)
        if key not in self._aot:
            self._aot[key] = self._sample_decode.lower(
                params_s, cur, cache, shape_structs(jax.random.PRNGKey(0)), ctx_s,
                jax.ShapeDtypeStruct((), jnp.float32), active, i32(), remaining,
            ).compile()

    def compile_counts(self) -> dict:
        """Compile accounting: AOT executables (warmup), traffic-time jit
        fallback compiles, and the program keys actually dispatched."""
        jit_fallback = (
            self._packed_admit._cache_size()
            + self._prefill_chunk._cache_size()
            + self._sample_decode._cache_size()
        )
        return dict(
            aot=len(self._aot),
            jit_fallback=jit_fallback,
            total=len(self._aot) + jit_fallback,
            used=sorted(map(str, self.used_programs)),
        )

    # -- the serve loop -----------------------------------------------------

    def serve(
        self,
        requests: Sequence[Request],
        *,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        key: Optional[jax.Array] = None,
        on_step: Optional[Callable[[int], None]] = None,
    ) -> tuple[dict[int, RequestOutput], ServeStats]:
        """Serve a request stream to completion. Returns (outputs by rid,
        stats). Outputs include per-request TTFT, queue wait and finish
        reason. ``on_step(clock)`` runs at the top of every scheduler
        round — the injection hook benchmarks use to flip silicon
        mid-serve (``set_silicon``), called before the round's span opens.
        With a recorder attached, each round and each of its stages is a
        span on the ``host`` track and a profiler annotation of the same
        name (``src/repro/obs/README.md``, "Round stages")."""
        if not requests:
            return {}, ServeStats(num_slots=self.num_slots, page_size=self.page_size)
        alloc = PageAllocator(self.num_pages, self.page_size)
        table = _SlotTable(requests, self.num_slots, alloc, self.max_pages_per_seq)
        stats = ServeStats(num_slots=self.num_slots, page_size=self.page_size)
        rec = self.obs
        tracer = RequestTracer(rec, proc="serve")
        pool = PoolMonitor(rec, alloc, proc="serve")
        enqueued: dict = {}  # rid -> its enqueue time, until its first prefill dispatch
        # each stage of a round is a span on the `host` track and, under
        # the same name, a profiler annotation on the device trace's clock
        stage = partial(rec.timed, proc="serve", track="host",
                        annotate=jax.profiler.TraceAnnotation)
        if rec:
            rec.instant("serve.programs", proc="serve", track="engine",
                        args={MASK_SCOPE: self.mask_ops, MAMBA_SCOPE: self.mamba_ops,
                              SCAN_SCOPE: self.scan_ops})

        V = self.cfg.vocab_size
        dtype = jnp.dtype(self.cfg.dtype)
        cache = M.init_paged_cache(
            self.cfg, self.num_pages, self.page_size, self.num_slots,
            self.max_pages_per_seq,
        )
        cur = jnp.zeros((self.num_slots, V), dtype)
        active = jnp.zeros((self.num_slots,), bool)
        remaining = jnp.zeros((self.num_slots,), jnp.int32)
        key = key if key is not None else jax.random.PRNGKey(0)
        temp = jnp.float32(temperature)
        eos = jnp.asarray(-1 if eos_id is None else eos_id, jnp.int32)
        buckets = self.prefill_buckets
        top = buckets[-1] if buckets else None
        pack: list[PackItem] = []

        def dispatch(pkey, jitted, *args):
            """Run program ``pkey``: its AOT executable, else the jit
            wrapper, recording a ``compile.fallback`` when that compiled."""
            fn = self._aot.get(pkey)
            if fn is not None:
                out = fn(*args)
            else:
                n = jitted._cache_size()
                out = jitted(*args)
                if rec and jitted._cache_size() > n:
                    rec.instant("compile.fallback", proc="serve", track="engine",
                                args=dict(program=str(pkey), clock=clock))
            self.used_programs.add(pkey)
            return out

        def flush_pack():
            nonlocal cache, cur, active, remaining
            if not pack:
                return
            total = sum(len(it.tokens) for it in pack)
            width = total if buckets is None else bucket_of(total, buckets)
            arrays = build_pack(
                pack, bucket=width, max_pack=self.max_pack,
                page_size=self.page_size, max_pages_per_seq=self.max_pages_per_seq,
                num_slots=self.num_slots, pad_id=self.pad_id,
            )
            t0 = rec.now() if rec else 0.0
            cache, cur, active, remaining = dispatch(
                ("prefill_admit", width), self._packed_admit,
                self.served_params, arrays["tokens"], arrays["positions"],
                arrays["segments"], self._serve_ctx, cache, cur, active, remaining,
                arrays["page_ix"], arrays["page_off"], arrays["gather_pos"],
                arrays["slots"], arrays["rows"], arrays["seq_lens"],
                arrays["budgets"],
            )
            stats.prefill_dispatches += 1
            if rec:
                with stage("prefill.wait"):
                    jax.block_until_ready(cur)
                t1 = rec.now()
                for it in pack:
                    tracer.queued(it.rid, it.slot, enqueued.pop(it.rid), t0)
                    tracer.admitted(
                        it.rid, it.slot, t0, t1,
                        args=dict(bucket=width, packed=len(pack),
                                  prompt_len=len(it.tokens)),
                    )
            pack.clear()

        def run_chunks(slot, r, pages):
            nonlocal cache, cur, active, remaining
            steps = plan_prefill(
                len(r.tokens), buckets=buckets, chunk_size=self.chunk_size
            )
            toks = np.asarray(r.tokens, np.int32)
            row = np.zeros((self.max_pages_per_seq,), np.int32)
            row[: len(pages)] = pages
            for st in steps:
                maps = chunk_step_maps(st, pages, page_size=self.page_size)
                ct = np.full((st.size,), self.pad_id, np.int32)
                ct[: st.valid] = toks[st.start : st.start + st.valid]
                t0 = rec.now() if rec else 0.0
                cache, cur, active, remaining = dispatch(
                    ("prefill_chunk", st.size), self._prefill_chunk,
                    self.served_params, ct[None], self._serve_ctx, cache, cur, active,
                    remaining, np.int32(slot), row, maps["page_ix"],
                    maps["page_off"], np.int32(st.start), np.int32(st.valid),
                    np.int32(r.max_new_tokens), np.bool_(st.final),
                )
                stats.prefill_dispatches += 1
                stats.chunk_dispatches += 1
                if rec:
                    with stage("prefill.wait"):
                        jax.block_until_ready(cur)
                    if r.rid in enqueued:  # its first chunk
                        tracer.queued(r.rid, slot, enqueued.pop(r.rid), t0)
                    tracer.chunk(
                        r.rid, slot, t0, rec.now(), final=st.final,
                        args=dict(size=st.size, start=st.start, valid=st.valid),
                    )

        clock = 0  # decode-dispatch index
        while not table.done:
            if on_step is not None:
                on_step(clock)
            round_annotation = partial(jax.profiler.StepTraceAnnotation, step_num=clock)
            with rec.timed("serve_round", proc="serve", track="host",
                           args=dict(clock=clock), annotate=round_annotation):
                with stage("schedule"):
                    table.stamp_arrivals(clock)
                    if rec:
                        for r in table.pending:
                            if r.arrival > clock:
                                break  # pending is arrival-sorted
                            if r.rid not in enqueued:
                                enqueued[r.rid] = rec.instant(
                                    "enqueue", proc="serve", track="engine",
                                    args=dict(rid=r.rid, arrival=r.arrival, clock=clock))
                    # admissions: fill free slots with every arrived request we
                    # can, packing short prompts into shared bucket dispatches
                    while True:
                        adm = table.pop_admission(clock)
                        if adm is None:
                            break
                        slot, r, pages = adm
                        table.outputs_admitted[r.rid] = clock
                        stats.admitted += 1
                        if self.cfg.has_ssm:
                            rec.count("ssm.state_reset")
                        plen = len(r.tokens)
                        if top is not None and plen > top:
                            flush_pack()
                            run_chunks(slot, r, pages)
                            continue
                        if pack and (
                            len(pack) >= self.max_pack
                            or (top is not None and sum(len(i.tokens) for i in pack) + plen > top)
                        ):
                            flush_pack()
                        pack.append(
                            PackItem(np.asarray(r.tokens, np.int32), slot, tuple(pages),
                                     r.max_new_tokens, rid=r.rid)
                        )
                    flush_pack()
                    stats.peak_resident_kv_bytes = max(
                        stats.peak_resident_kv_bytes, alloc.pages_in_use * self._page_bytes
                    )
                    pool.sample()
                if not table.active.any():
                    # idle: jump the clock to the next arrival (no dispatches)
                    nxt = table.next_arrival()
                    assert nxt is not None and nxt > clock
                    clock = nxt
                    continue

                n_active = int(table.active.sum())
                t0 = rec.now() if rec else 0.0
                with stage("decode.dispatch"):
                    emitted, tok_lp, cur, cache, key, active, remaining = dispatch(
                        ("decode",), self._sample_decode,
                        self.served_params, cur, cache, key, self._serve_ctx, temp, active, eos,
                        remaining,
                    )
                clock += 1
                stats.decode_dispatches += 1
                stats.emitted_tokens += n_active
                stats.active_slot_steps += n_active
                stats.kv_byte_steps += alloc.pages_in_use * self._page_bytes
                with stage("decode.wait"):
                    jax.block_until_ready(emitted)
                with stage("decode.fetch"):
                    em = np.asarray(emitted)
                    lp = np.asarray(tok_lp)
                    ac = np.asarray(active)
                t1 = rec.now() if rec else 0.0
                msk = table.active  # the mask this dispatch computed under
                with stage("record_step"):
                    if rec:
                        tracer.decode_dispatch(t0, t1, n_active=n_active, clock=clock)
                        slot_of = {r.rid: s for s, r in enumerate(table.slots)
                                   if r is not None}
                    retired = table.record_step(em, lp, ac, clock, eos_id=eos_id)
                    if rec and retired:
                        t1 = rec.now()
                        for rid in retired:
                            tracer.retired(table.outputs[rid], slot_of[rid], t1)
                        pool.sample()
                if self.health is not None:
                    with stage("health"):
                        self.health.observe_decode(
                            0, clock=clock,
                            mean_logprob=float(lp[msk].mean()) if msk.any() else None,
                            alloc_failures=alloc.alloc_failures,
                        )
                        if self.prober is not None and clock % self.probe_every == 0:
                            t0p = rec.now() if rec else 0.0
                            res = self.prober.probe(clock=clock)
                            stats.probe_dispatches += res.dispatches
                            if rec:
                                rec.span("probe", proc="serve", track="health",
                                         t0=t0p, t1=rec.now(), args=res.as_dict())
                                rec.count("probe.dispatches", res.dispatches)
                            self.health.observe_probe(0, res, clock=clock)
                            if self.alerts:
                                self.alerts.evaluate(clock=clock)
        stats.peak_resident_kv_bytes = max(
            stats.peak_resident_kv_bytes, alloc.peak_pages * self._page_bytes
        )
        pool.flush()  # close the counter series at the final timestamp
        if self.health is not None:
            self.health.finalize()
        if self.alerts:
            self.alerts.evaluate(clock=clock)
        if rec:
            cc = self.compile_counts()
            rec.gauge_set("serve.compiles.aot", cc["aot"])
            rec.gauge_set("serve.compiles.jit_fallback", cc["jit_fallback"])
            rec.gauge_set("serve.compiles.total", cc["total"])
            rec.instant("serve.end", proc="serve", track="engine",
                        args=stats.as_dict())
        return table.outputs, stats
