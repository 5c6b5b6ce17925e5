"""Multi-chip serving — vmapped and shard_mapped engines for a whole fleet.

The deployment half of eFAT produces one fault-aware artifact per
retraining job, each deployed on chips with their own fault maps. Evaluating
the deployed fleet with per-chip ``ServeEngine`` instances costs N Python
generate loops of one-dispatch-per-token each. But the engines differ only
in (params, FaultContext) — the same population trick the training side
uses: ``FleetServeEngine`` stacks N chips' params and masks and vmaps the
fused sampling+decode step (``repro.serve.engine.make_sample_decode``) over
the chip axis, so the *entire fleet* advances one token per dispatch.

Semantics match per-chip serving exactly: greedy decoding is argmax per
chip (independent of the sampling key), so temperature=0.0 reproduces each
chip's own ``ServeEngine`` token-for-token (pinned in tests/test_fleet.py);
with temperature > 0 each chip samples from its own key stream (the fleet
key is split once per chip).

``FleetServeEngine`` shares one prompt batch across chips — the
fleet-evaluation use case is "run the same prompt set through every
deployed model and compare". ``ShardedFleetServeEngine`` is the
production-shaped tier: chips map onto the devices of a "pop" mesh
(``repro.launch.mesh.make_pop_mesh``, mirroring the training-side
``ShardedPopulationEngine``), and every chip consumes its *own* ragged
request stream through its own continuous-batch slot table over a paged KV
cache — the masked form of the same fused step, under ``shard_map``, so
one dispatch advances every chip's in-flight slots and no chip waits for
another chip's prompts. Greedy per-chip outputs are pinned against
per-chip ``ContinuousBatchingEngine`` runs (tests/test_serve_continuous.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.masking import FaultContext, healthy, stack_contexts
from repro.launch.mesh import make_pop_mesh
from repro.models import model as M
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
from repro.serve.continuous import (
    Request,
    RequestOutput,
    ServeStats,
    _SlotTable,
)
from repro.serve.engine import make_sample_decode
from repro.serve.kvcache import DEFAULT_PAGE_SIZE, PageAllocator, page_bytes
from repro.train.population import _stack_trees

__all__ = ["FleetGenerateResult", "FleetServeEngine", "ShardedFleetServeEngine"]


@dataclass
class FleetGenerateResult:
    tokens: jax.Array  # (N, B, prompt + generated)
    logprobs: jax.Array  # (N, B, generated)

    def chip(self, i: int):
        """Per-chip view (tokens, logprobs) — shaped like ServeEngine output."""
        return self.tokens[i], self.logprobs[i]


class FleetServeEngine:
    """Serve N chips' (params, FaultContext) pairs as one batched program.

    ``params_list[i]`` are chip i's shipped (FAP-masked) weights and
    ``ctxs[i]`` its fault context (None/healthy for a fault-free chip —
    mixed fleets are fine; ``stack_contexts`` upcasts healthy members).
    All chips share one model config and prompt batch.
    """

    def __init__(
        self,
        cfg,
        params_list: Sequence,
        ctxs: Optional[Sequence[Optional[FaultContext]]] = None,
        *,
        max_len: int = 4096,
    ):
        n = len(params_list)
        if n == 0:
            raise ValueError("FleetServeEngine needs at least one chip")
        ctxs = list(ctxs) if ctxs is not None else [healthy()] * n
        if len(ctxs) != n:
            raise ValueError(f"{n} params sets but {len(ctxs)} fault contexts")
        self.cfg = cfg
        self.max_len = max_len
        self.num_chips = n
        self.params = _stack_trees(list(params_list))
        self.ctx = stack_contexts([c or healthy() for c in ctxs])
        # vmap axis for the context: the ok mask batches over chips when any
        # chip is faulty; an all-healthy fleet carries no mask at all
        ctx_ax = (
            None
            if self.ctx.ok is None
            else FaultContext(ok=0, mode=self.ctx.mode)  # type: ignore[arg-type]
        )
        self._prefill = jax.jit(
            jax.vmap(
                lambda p, b, ctx: M.prefill(p, b, cfg, ctx, cache_len=max_len),
                in_axes=(0, None, ctx_ax),
            )
        )
        # cur/cache/keys are re-bound from each dispatch's outputs in the
        # generate loop — donated so the fleet's stacked KV caches alias in
        # place (repro.analysis DON001); params/ctx are reused, not donated
        self._sample_decode = jax.jit(
            jax.vmap(make_sample_decode(cfg), in_axes=(0, 0, 0, 0, ctx_ax, None)),
            donate_argnums=(1, 2, 3),
        )

    def generate(
        self,
        prompts: jax.Array,  # (B, S) token ids, shared by every chip
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        key: Optional[jax.Array] = None,
    ) -> FleetGenerateResult:
        logits, cache = self._prefill(self.params, {"tokens": prompts}, self.ctx)
        cur = logits  # (N, B, V)
        key = key if key is not None else jax.random.PRNGKey(0)
        keys = jax.random.split(key, self.num_chips)  # one sample stream per chip
        temp = jnp.float32(temperature)
        toks = [jnp.broadcast_to(prompts[None], (self.num_chips,) + prompts.shape)]
        lps = []
        for _ in range(max_new_tokens):
            nxt, tok_lp, cur, cache, keys = self._sample_decode(
                self.params, cur, cache, keys, self.ctx, temp
            )
            lps.append(tok_lp)
            toks.append(nxt[:, :, None])
        return FleetGenerateResult(
            tokens=jnp.concatenate(toks, axis=2), logprobs=jnp.stack(lps, axis=2)
        )


class ShardedFleetServeEngine:
    """Sharded, ragged fleet serving: chips → devices, streams → slot tables.

    Each chip ``c`` runs its own continuous-batch slot table (paged KV
    cache, admission on arrival, retirement on EOS/budget — the same loop
    as ``repro.serve.continuous.ContinuousBatchingEngine``) over its own
    request stream; ONE ``shard_map``-over-the-pop-mesh dispatch advances
    every chip's in-flight slots a token. The chip axis tiles the mesh
    (``len(params_list)`` must be a multiple of the pop extent; chips
    beyond the extent vmap within a device, mirroring how the training-side
    ``ShardedPopulationEngine`` packs sub-populations into pop slices).

    Greedy decoding is argmax per slot, so every chip's outputs reproduce a
    per-chip ``ContinuousBatchingEngine`` on the same stream; with
    temperature > 0 each chip consumes its own key stream (the fleet key is
    split once per chip), so runs are reproducible per chip and chips'
    samples are independent.
    """

    def __init__(
        self,
        cfg,
        params_list: Sequence,
        ctxs: Optional[Sequence[Optional[FaultContext]]] = None,
        *,
        mesh=None,
        axis_name: str = "pop",
        num_slots: int = 4,
        page_size: int = DEFAULT_PAGE_SIZE,
        num_pages: int = 128,
        max_pages_per_seq: Optional[int] = None,
        pad_id: int = 0,
        prefill_buckets=DEFAULT_PREFILL_BUCKETS,
        chunk_size: Optional[int] = None,
        max_pack: int = 4,
        recorder: Optional[Recorder] = None,
        probe_every: Optional[int] = None,
        health_config: Optional[HealthConfig] = None,
        alert_rules: Optional[Sequence[AlertRule]] = None,
    ):
        n = len(params_list)
        if n == 0:
            raise ValueError("ShardedFleetServeEngine needs at least one chip")
        if cfg.has_ssm:
            raise ValueError(
                f"continuous fleet serving supports attention families only; "
                f"{cfg.family!r} carries unpaged SSM state"
            )
        if cfg.is_encoder:
            raise ValueError("encoder-only arch has no decode path")
        ctxs = list(ctxs) if ctxs is not None else [healthy()] * n
        if len(ctxs) != n:
            raise ValueError(f"{n} params sets but {len(ctxs)} fault contexts")
        if mesh is None:
            # largest pop extent that both fits the backend and tiles the fleet
            ndev = len(jax.devices())
            extent = max(d for d in range(1, min(n, ndev) + 1) if n % d == 0)
            mesh = make_pop_mesh(extent, axis=axis_name)
        if axis_name not in mesh.shape:
            raise ValueError(
                f"mesh axes {tuple(mesh.shape)} lack population axis {axis_name!r}"
            )
        extent = int(mesh.shape[axis_name])
        if n % extent != 0:
            raise ValueError(
                f"{n} chips don't tile the {extent}-slice {axis_name!r} mesh; "
                "pad the fleet or pass a mesh whose pop extent divides it"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_chips = n
        self.num_slots = num_slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages_per_seq = max_pages_per_seq or (num_pages - 1)
        self.pad_id = pad_id
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
            self.max_pack = int(max_pack)
        # host-side observability; one track per chip (chip{c}/slot{s},
        # chip{c}/pages) so Perfetto draws the fleet as per-chip swimlanes.
        # All hooks sit at dispatch boundaries outside traced code.
        self.obs = recorder if recorder is not None else NULL_RECORDER
        self._page_bytes = page_bytes(cfg, page_size)
        self.params_list = list(params_list)
        self.ctxs = [c or healthy() for c in ctxs]
        # chip-stacked state lives split over the pop axis, each device
        # holding its own chips' slice, so the fused step reads it in place
        # instead of scattering it from one device on every dispatch
        self._chip_sharding = NamedSharding(mesh, P(axis_name))
        self.params = jax.device_put(
            _stack_trees(self.params_list), self._chip_sharding
        )
        self.ctx = stack_contexts(self.ctxs)
        if self.ctx.ok is not None:
            self.ctx = FaultContext(
                ok=jax.device_put(self.ctx.ok, self._chip_sharding),
                mode=self.ctx.mode,
            )

        sample = make_sample_decode(cfg, pad_id=pad_id)
        mode = self.ctx.mode
        pa = P(axis_name)
        if self.ctx.ok is None:
            hctx = healthy()

            def chip_step(p, cur, cache, key, temp, eos, active, remaining):
                return sample(
                    p, cur, cache, key, hctx, temp,
                    active=active, eos_id=eos, remaining=remaining,
                )

            vmapped = jax.vmap(chip_step, in_axes=(0, 0, 0, 0, None, None, 0, 0))
            in_specs = (pa, pa, pa, pa, P(), P(), pa, pa)
            donate = (1, 2, 3, 6, 7)  # cur, cache, keys, active, remaining
        else:

            def chip_step(p, cur, cache, key, ok, temp, eos, active, remaining):
                return sample(
                    p, cur, cache, key, FaultContext(ok=ok, mode=mode), temp,
                    active=active, eos_id=eos, remaining=remaining,
                )

            vmapped = jax.vmap(chip_step, in_axes=(0, 0, 0, 0, 0, None, None, 0, 0))
            in_specs = (pa, pa, pa, pa, pa, P(), P(), pa, pa)
            donate = (1, 2, 3, 7, 8)  # cur, cache, keys, active, remaining
        # the serve loop re-binds every donated operand from the previous
        # dispatch (host copies of emitted/active are taken synchronously
        # before the next call), so the sharded page pools alias in place
        # (repro.analysis DON001); params and the stacked ok masks are
        # reused across dispatches and stay undonated
        self._step = jax.jit(
            jax.shard_map(
                vmapped,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=(pa,) * 7,
                check_vma=False,
            ),
            donate_argnums=donate,
        )
        self._packed_admit = jax.jit(
            self._packed_admit_fn, donate_argnums=(5, 6, 7, 8)
        )
        self._prefill_chunk = jax.jit(
            self._prefill_chunk_fn, donate_argnums=(3, 4, 5, 6)
        )
        # fault detection (ROADMAP item 2): one ABFT prober per chip, all
        # dispatched every probe_every fused decode dispatches. Probes are
        # SEPARATE dispatches through one shared jitted program and never
        # touch the serve loop's carried state or key streams, so enabling
        # them changes no sampled token on any chip.
        if probe_every is not None and probe_every < 1:
            raise ValueError(f"probe_every must be >= 1, got {probe_every}")
        self.probe_every = int(probe_every) if probe_every else None
        self._probers: Optional[list] = None
        self.health: Optional[HealthTracker] = None
        self.alerts = AlertEngine(self.obs, alert_rules) if alert_rules else None
        if self.probe_every:
            self._init_probers(health_config)

    def _init_probers(self, health_config: Optional[HealthConfig]) -> None:
        from repro.kernels.masked_matmul.ops import masked_matmul_checksummed
        from repro.obs.abft import ChipProber, select_probe_weight

        cfg = self.cfg
        rows, cols = cfg.array_rows, cfg.array_cols
        probe_fn = jax.jit(masked_matmul_checksummed)  # shared: one compile
        ones = jnp.ones((rows, cols), jnp.float32)
        dtype = jnp.dtype(cfg.dtype)

        def make_dispatch(c, w):
            def dispatch(x):
                # chip c's LIVE mask: re-read self.ctxs so a set_silicon()
                # change is what the next probe computes through
                ok = self.ctxs[c].ok
                y, chk = probe_fn(
                    jnp.asarray(x, dtype), w, ok if ok is not None else ones
                )
                return np.asarray(y), np.asarray(chk)

            return dispatch

        self._probers = []
        for c, params_c in enumerate(self.params_list):
            _, w = select_probe_weight(params_c)
            self._probers.append(ChipProber(
                make_dispatch(c, w), array_shape=(rows, cols),
                k_dim=int(w.shape[0]), chip=c,
            ))
        self.health = HealthTracker(
            self.num_chips, self.obs, config=health_config, proc="fleet"
        )

    def set_silicon(self, chip: int, ctx: FaultContext) -> None:
        """Simulate a mid-flight silicon change on one chip: swap the LIVE
        fault context chip ``chip``'s subsequent dispatches compute through,
        WITHOUT rebasing that chip's prober goldens — so its next probe
        sees the divergence and the other chips' don't. The fleet must have
        been built with ACTIVE contexts (possibly zero-fault FaultMaps) on
        every chip: the compiled programs carry the stacked ok mask as a
        live input, and an ok=None ↔ ok=array flip would be a different
        program."""
        if not 0 <= chip < self.num_chips:
            raise ValueError(f"chip {chip} out of range [0, {self.num_chips})")
        if self.ctx.ok is None:
            raise ValueError(
                "set_silicon needs an ACTIVE fleet: construct every chip "
                "with an explicit (possibly zero-fault) FaultMap context so "
                "the stacked mask is a live program input"
            )
        if ctx is None or ctx.ok is None:
            raise ValueError(
                "set_silicon needs an ACTIVE context; pass a zero-fault "
                "FaultMap context to model pristine silicon"
            )
        if ctx.mode != self.ctx.mode:
            raise ValueError(
                f"mode mismatch: fleet {self.ctx.mode!r} vs new {ctx.mode!r}"
            )
        if tuple(ctx.ok.shape) != tuple(self.ctx.ok.shape[1:]):
            raise ValueError(
                f"ok shape mismatch: chip expects "
                f"{tuple(self.ctx.ok.shape[1:])}, got {tuple(ctx.ok.shape)}"
            )
        self.ctxs[chip] = ctx
        # the stacked mask is an UNDONATED dispatch input, so a functional
        # row update is safe between dispatches
        self.ctx = FaultContext(
            ok=self.ctx.ok.at[chip].set(jnp.asarray(ctx.ok, self.ctx.ok.dtype)),
            mode=self.ctx.mode,
        )

    # -- jitted admission: the bucketed planner's programs, chip-indexed ----

    def _packed_admit_fn(
        self, params_c, tokens, positions, segments, ctx_c, cache, cur, active,
        remaining, chip, page_ix, page_off, gather_pos, slots, rows, seq_lens,
        budgets,
    ):
        """Chip-indexed twin of ``ContinuousBatchingEngine._packed_admit_fn``:
        admit a PACK of one chip's requests in one bucket-shaped dispatch,
        scattering into the fleet's stacked state at ``chip``. The chip index
        is traced, so one compiled program per bucket serves the whole fleet
        (per-fault-context pytree structure permitting)."""
        hidden, dense = M.prefill(
            params_c, {"tokens": tokens, "positions": positions}, self.cfg,
            ctx_c, full_kv=True, return_hidden=True, segments=segments,
            attn_impl="dense",
        )
        # (L, 1, Hkv, W, hd) -> (W, L, Hkv, hd): the advanced indices
        # (chip, page_ix, page_off) around the slices put the token dim first
        k = jnp.transpose(dense["k"][:, 0], (2, 0, 1, 3))
        v = jnp.transpose(dense["v"][:, 0], (2, 0, 1, 3))
        kp = cache["k_pages"].at[chip, :, page_ix, :, page_off].set(k.astype(cache["k_pages"].dtype))
        vp = cache["v_pages"].at[chip, :, page_ix, :, page_off].set(v.astype(cache["v_pages"].dtype))
        h = hidden[0, gather_pos]  # (max_pack, d)
        logits = M.unembed(self.cfg, params_c, h[None], ctx_c)[0]  # (max_pack, V)
        cache = dict(
            k_pages=kp,
            v_pages=vp,
            block_tables=cache["block_tables"].at[chip, slots].set(rows),
            seq_lens=cache["seq_lens"].at[chip, slots].set(seq_lens),
        )
        cur = cur.at[chip, slots].set(logits.astype(cur.dtype))
        active = active.at[chip, slots].set(True)
        remaining = remaining.at[chip, slots].set(budgets)
        return cache, cur, active, remaining

    def _prefill_chunk_fn(
        self, params_c, tokens, ctx_c, cache, cur, active, remaining,
        chip, slot, row, page_ix, page_off, prefix, valid, budget, activate,
    ):
        """Chip-indexed twin of ``ContinuousBatchingEngine._prefill_chunk_fn``:
        one fixed-size chunk of a long prompt streaming into one chip's page
        chain; the final chunk (``activate``) flips the slot live."""
        logits, kc, vc = M.prefill_chunk(
            params_c, tokens, self.cfg, ctx_c,
            k_pages=cache["k_pages"][chip], v_pages=cache["v_pages"][chip],
            row=row, prefix_len=prefix, valid_len=valid,
        )
        k = jnp.transpose(kc[:, 0], (2, 0, 1, 3))
        v = jnp.transpose(vc[:, 0], (2, 0, 1, 3))
        new_len = jnp.where(activate, prefix + valid, cache["seq_lens"][chip, slot])
        cache = dict(
            k_pages=cache["k_pages"].at[chip, :, page_ix, :, page_off].set(k.astype(cache["k_pages"].dtype)),
            v_pages=cache["v_pages"].at[chip, :, page_ix, :, page_off].set(v.astype(cache["v_pages"].dtype)),
            block_tables=cache["block_tables"].at[chip, slot].set(row),
            seq_lens=cache["seq_lens"].at[chip, slot].set(new_len),
        )
        cur = cur.at[chip, slot].set(
            jnp.where(activate, logits[0].astype(cur.dtype), cur[chip, slot])
        )
        active = active.at[chip, slot].set(active[chip, slot] | activate)
        remaining = remaining.at[chip, slot].set(
            jnp.where(activate, budget, remaining[chip, slot])
        )
        return cache, cur, active, remaining

    # -- the fleet serve loop ------------------------------------------------

    def serve(
        self,
        streams: Sequence[Sequence[Request]],
        *,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        key: Optional[jax.Array] = None,
        on_step: Optional[Callable[[int], None]] = None,
    ) -> tuple[list[dict[int, RequestOutput]], ServeStats]:
        """Serve one ragged request stream per chip to completion.

        Returns (per-chip outputs-by-rid, fleet-level stats). Stats count
        fused dispatches — the whole fleet advances per dispatch, so the
        total is driven by the busiest chip, not the sum over chips.
        ``on_step(clock)`` runs at the top of every scheduler round — the
        injection hook benchmarks use to flip one chip's silicon mid-serve
        (``set_silicon``)."""
        if len(streams) != self.num_chips:
            raise ValueError(f"{self.num_chips} chips but {len(streams)} request streams")
        stats = ServeStats(
            num_slots=self.num_chips * self.num_slots, page_size=self.page_size
        )
        allocs = [PageAllocator(self.num_pages, self.page_size) for _ in range(self.num_chips)]
        tables = [
            _SlotTable(list(s), self.num_slots, allocs[c], self.max_pages_per_seq)
            for c, s in enumerate(streams)
        ]
        rec = self.obs
        tracers = [
            RequestTracer(rec, proc="fleet", track_prefix=f"chip{c}/")
            for c in range(self.num_chips)
        ]
        fleet_tracer = RequestTracer(rec, proc="fleet")
        pools = [
            PoolMonitor(rec, allocs[c], proc="fleet", track=f"chip{c}/pages",
                        name_prefix=f"kv.chip{c}.")
            for c in range(self.num_chips)
        ]

        N, S, V = self.num_chips, self.num_slots, self.cfg.vocab_size
        dtype = jnp.dtype(self.cfg.dtype)
        one = M.init_paged_cache(
            self.cfg, self.num_pages, self.page_size, S, self.max_pages_per_seq
        )
        cache = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (N,) + x.shape).copy(), one
        )
        cur = jnp.zeros((N, S, V), dtype)
        active = jnp.zeros((N, S), bool)
        remaining = jnp.zeros((N, S), jnp.int32)
        key = key if key is not None else jax.random.PRNGKey(0)
        keys = jax.random.split(key, N)  # one sample stream per chip
        cache, cur, active, remaining, keys = jax.device_put(
            (cache, cur, active, remaining, keys), self._chip_sharding
        )
        temp = jnp.float32(temperature)
        eos = jnp.asarray(-1 if eos_id is None else eos_id, jnp.int32)

        buckets = self.prefill_buckets
        top = buckets[-1] if buckets else None

        def flush_pack(c, pack):
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
            cache, cur, active, remaining = self._packed_admit(
                self.params_list[c], arrays["tokens"], arrays["positions"],
                arrays["segments"], self.ctxs[c], cache, cur, active, remaining,
                np.int32(c), arrays["page_ix"], arrays["page_off"],
                arrays["gather_pos"], arrays["slots"], arrays["rows"],
                arrays["seq_lens"], arrays["budgets"],
            )
            stats.prefill_dispatches += 1
            if rec:
                jax.block_until_ready(cur)
                t1 = rec.now()
                for it in pack:
                    tracers[c].admitted(
                        it.rid, it.slot, t0, t1,
                        args=dict(bucket=width, packed=len(pack), chip=c,
                                  prompt_len=len(it.tokens)),
                    )
            pack.clear()

        def run_chunks(c, slot, r, pages):
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
                cache, cur, active, remaining = self._prefill_chunk(
                    self.params_list[c], ct[None], self.ctxs[c], cache, cur,
                    active, remaining, np.int32(c), np.int32(slot), row,
                    maps["page_ix"], maps["page_off"], np.int32(st.start),
                    np.int32(st.valid), np.int32(r.max_new_tokens),
                    np.bool_(st.final),
                )
                stats.prefill_dispatches += 1
                stats.chunk_dispatches += 1
                if rec:
                    jax.block_until_ready(cur)
                    tracers[c].chunk(
                        r.rid, slot, t0, rec.now(), final=st.final,
                        args=dict(size=st.size, start=st.start, valid=st.valid),
                    )

        clock = 0
        while not all(t.done for t in tables):
            if on_step is not None:
                on_step(clock)
            for c, table in enumerate(tables):
                table.stamp_arrivals(clock)
                pack: list[PackItem] = []
                while True:
                    adm = table.pop_admission(clock)
                    if adm is None:
                        break
                    slot, r, pages = adm
                    table.outputs_admitted[r.rid] = clock
                    stats.admitted += 1
                    plen = len(r.tokens)
                    if top is not None and plen > top:
                        flush_pack(c, pack)
                        run_chunks(c, slot, r, pages)
                        continue
                    if pack and (
                        len(pack) >= self.max_pack
                        or (top is not None
                            and sum(len(i.tokens) for i in pack) + plen > top)
                    ):
                        flush_pack(c, pack)
                    pack.append(
                        PackItem(np.asarray(r.tokens, np.int32), slot,
                                 tuple(pages), r.max_new_tokens)
                    )
                flush_pack(c, pack)
            pages_in_use = sum(a.pages_in_use for a in allocs)
            stats.peak_resident_kv_bytes = max(
                stats.peak_resident_kv_bytes, pages_in_use * self._page_bytes
            )
            for p in pools:
                p.sample()
            if not any(t.active.any() for t in tables):
                arrivals = [t.next_arrival() for t in tables if t.next_arrival() is not None]
                assert arrivals, "no active slots and no pending arrivals"
                clock = max(clock + 1, min(arrivals))
                continue

            n_active = int(sum(t.active.sum() for t in tables))
            args = (self.params, cur, cache, keys)
            if self.ctx.ok is not None:
                args += (self.ctx.ok,)
            t0 = rec.now() if rec else 0.0
            emitted, tok_lp, cur, cache, keys, active, remaining = self._step(
                *args, temp, eos, active, remaining
            )
            clock += 1
            stats.decode_dispatches += 1
            stats.emitted_tokens += n_active
            stats.active_slot_steps += n_active
            stats.kv_byte_steps += pages_in_use * self._page_bytes
            em = np.asarray(emitted)  # forces the fused dispatch to completion
            lp = np.asarray(tok_lp)
            ac = np.asarray(active)
            if rec:
                t1 = rec.now()
                fleet_tracer.decode_dispatch(t0, t1, n_active=n_active, clock=clock)
            for c, table in enumerate(tables):
                if rec:
                    slot_of = {r.rid: s for s, r in enumerate(table.slots)
                               if r is not None}
                if self.health is not None:
                    msk = table.active  # the mask this dispatch computed under
                    self.health.observe_decode(
                        c, clock=clock,
                        mean_logprob=(
                            float(lp[c][msk].mean()) if msk.any() else None
                        ),
                        alloc_failures=allocs[c].alloc_failures,
                    )
                retired = table.record_step(em[c], lp[c], ac[c], clock, eos_id=eos_id)
                if rec and retired:
                    t1 = rec.now()
                    for rid in retired:
                        tracers[c].retired(table.outputs[rid], slot_of[rid], t1)
                    pools[c].sample()
            if self._probers is not None and clock % self.probe_every == 0:
                for c, prober in enumerate(self._probers):
                    t0p = rec.now() if rec else 0.0
                    res = prober.probe(clock=clock)
                    stats.probe_dispatches += res.dispatches
                    if rec:
                        rec.span("probe", proc="fleet", track=f"chip{c}/health",
                                 t0=t0p, t1=rec.now(), args=res.as_dict())
                        rec.count("probe.dispatches", res.dispatches)
                    self.health.observe_probe(c, res, clock=clock)
                if self.alerts:
                    self.alerts.evaluate(clock=clock)
        # peak residency is exact from the per-round samples: pages only
        # grow at admission (sampled) and shrink at retirement
        for p in pools:
            p.flush()  # close every chip's counter series at the final ts
        if self.health is not None:
            self.health.finalize()
        if self.alerts:
            self.alerts.evaluate(clock=clock)
        if rec:
            rec.instant("serve.end", proc="fleet", track="engine",
                        args=dict(chips=self.num_chips, **stats.as_dict()))
        return [t.outputs for t in tables], stats
