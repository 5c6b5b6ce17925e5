"""Run the eFAT system's two jobs once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Phases on one chip, all at smollm-135m's published widths (30 layers,
d_model 576, 9/3 heads, vocab 49152, bf16 activations, a 256x256 array),
with random weights made from ``--seed``:

  a. device  — JAX's first device must be a TPU; there is no CPU fallback.
  b. kernels — the four Pallas kernels compiled for the chip (never interpret
               mode, never the jnp reference) at main-path widths, each
               against its reference.
  c. serve   — one faulty chip (fault rate 0.1, FAP masking) through
               ContinuousBatchingEngine with AOT warmup and ABFT probes;
               every request runs to its budget, nothing compiles after
               warmup, the probes raise no alarm on the unchanged chip, and
               the outputs agree with ServeEngine. One prefill through the
               Pallas masked matmul is checked against the FAP prefill.
  d. retrain — eFAT over LMFATTrainer (population engine): pretrain, a
               2-rate resilience table, and the plan for 3 faulty chips.

With ``--chips 4`` the script runs only what exists across chips, at the
same widths with the depth cut to 2 layers: ShardedPopulationEngine on a
4x1 and a 2x2 mesh against the one-device population engine, and
ShardedFleetServeEngine against four per-chip engines, and it checks that
their chip-stacked state spans four devices.

Each phase prints one line of results with its set-up seconds (compilation
included) and its run seconds; these are smoke timings, not benchmark
numbers. The last line of standard output is one JSON object naming the
device. Any failure exits non-zero before it. ``--rehearse`` runs the same
code on the CPU at a tiny size with the kernels in Pallas interpret mode,
for rehearsals and tests; every line it prints says so.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

FLEET_RATES = (0.05, 0.10, 0.15)
SERVE_FAULT_RATE = 0.1
# (prompt length, new tokens): the 300-token prompt passes the top prefill
# bucket (256) and streams in chunks
SERVE_REQUESTS = ((16, 16), (48, 32), (97, 24), (130, 20), (180, 28), (230, 18), (300, 32), (64, 26))


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearse = args.rehearse
        self.key = jax.random.PRNGKey(args.seed)

    # -- reporting ---------------------------------------------------------

    def report(self, phase: str, **fields) -> None:
        parts = [f"{k}={v}" for k, v in fields.items()]
        if self.rehearse:
            parts.append("REHEARSAL(cpu, interpret mode, tiny sizes; no TPU checked)")
        print(f"[{phase}] " + " ".join(parts), flush=True)

    def next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def model_config(self, num_layers=None):
        from repro.configs import get_arch, reduce_config

        cfg = get_arch("smollm-135m")
        if self.rehearse:
            cfg = reduce_config(cfg)
        if num_layers is not None:
            cfg = replace(cfg, num_layers=num_layers)
        return cfg

    # -- a. device -----------------------------------------------------------

    def device(self):
        devs = jax.devices()
        d0 = devs[0]
        if not self.rehearse:
            check(d0.platform == "tpu",
                  f"JAX found no TPU: its first device is {d0.platform!r}")
            check(len(devs) >= self.args.chips,
                  f"--chips {self.args.chips} needs that many devices, JAX sees {len(devs)}")
        self.report("a.device", platform=d0.platform, kind=repr(d0.device_kind),
                    count=len(devs), jax=jax.__version__)
        return d0

    # -- b. kernels ----------------------------------------------------------

    def _kernel_case(self, name, fn, ref_fn, args, dtype):
        """Compile ``fn`` for the device (set-up), run it once more (run),
        and compare it with ``ref_fn`` within the kernel tolerances."""
        from repro.kernels.common import dtype_tol

        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        setup = time.perf_counter() - t0
        if not self.rehearse:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: the compiled program holds no Pallas kernel")
        jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run = time.perf_counter() - t0
        expected = jax.jit(ref_fn)(*args)
        rtol, atol = dtype_tol(dtype)
        errs = []
        for o, e in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(expected)):
            o, e = np.asarray(o, np.float32), np.asarray(e, np.float32)
            check(np.isfinite(o).all(), f"{name}: non-finite output")
            bad = np.abs(o - e) > atol + rtol * np.abs(e)
            check(not bad.any(),
                  f"{name}: {int(bad.sum())} elements outside rtol={rtol} atol={atol}")
            errs.append(float(np.abs(o - e).max()))
        self.report("b.kernel", name=name, max_err=f"{max(errs):.3g}",
                    tol=f"rtol={rtol},atol={atol}", setup_s=f"{setup:.3f}",
                    run_s=f"{run:.6f}")
        return setup, run

    def kernels(self):
        from repro.core import random_fault_map
        from repro.kernels.decode_attention.ops import (
            decode_attention, paged_decode_attention, quantize_kv,
        )
        from repro.kernels.decode_attention.ref import (
            decode_attention_ref, paged_decode_attention_ref,
        )
        from repro.kernels.flash_attention.ops import flash_attention
        from repro.kernels.flash_attention.ref import attention_ref
        from repro.kernels.mamba_scan.ops import selective_scan
        from repro.kernels.mamba_scan.ref import selective_scan_ref
        from repro.kernels.masked_matmul.ops import masked_matmul
        from repro.kernels.masked_matmul.ref import masked_matmul_ref

        interp = self.rehearse  # compiled for the chip unless rehearsing
        bf16 = jnp.bfloat16
        normal = lambda shape, dtype=bf16: jax.random.normal(self.next_key(), shape, dtype)
        lm = self.model_config()
        if self.rehearse:
            gemms, m, array = ((64, 64), (64, 128)), 32, (16, 16)
            hq, hkv, hd, seq, ctx_len, page = 4, 2, 32, 256, 256, 16
            scan_l, scan_d, scan_n = 64, 256, 16
        else:
            d, f, hd = lm.d_model, lm.d_ff, lm.resolved_head_dim
            kv = lm.num_kv_heads * hd
            gemms = ((d, d), (d, kv), (d, f), (f, d), (d, lm.vocab_size))
            m, array = 2048, (lm.array_rows, lm.array_cols)
            hq, hkv, seq, ctx_len, page = lm.num_heads, lm.num_kv_heads, 2048, 2048, 16
            mamba = self.args.scan_config
            scan_l, scan_d, scan_n = 512, mamba.d_inner, mamba.ssm_state

        setup = run = 0.0
        fm = random_fault_map(self.args.seed, *array, SERVE_FAULT_RATE)
        ok = jnp.asarray(fm.ok_mask, jnp.float32)
        for k, n in gemms:
            s, r = self._kernel_case(
                f"masked_matmul[{m}x{k}x{n},mask{array[0]}x{array[1]}]",
                lambda x, w, ok: masked_matmul(x, w, ok, interpret=interp),
                masked_matmul_ref, (normal((m, k)), normal((k, n)), ok), bf16,
            )
            setup, run = setup + s, run + r

        q = normal((1, hq, seq, hd))
        k_, v_ = normal((1, hkv, seq, hd)), normal((1, hkv, seq, hd))
        s, r = self._kernel_case(
            f"flash_attention[{hq}/{hkv}h,hd{hd},seq{seq},causal]",
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=interp),
            lambda q, k, v: attention_ref(q, k, v, causal=True, window=None),
            (q, k_, v_), bf16,
        )
        setup, run = setup + s, run + r

        slots = 4
        qd = normal((slots, hq, 1, hd))
        ki, ks = quantize_kv(normal((slots, hkv, ctx_len, hd), jnp.float32))
        vi, vs = quantize_kv(normal((slots, hkv, ctx_len, hd), jnp.float32))
        valid = jnp.int32(ctx_len - ctx_len // 4)
        s, r = self._kernel_case(
            f"decode_attention[int8,dense,{slots}x{hq}/{hkv}h,ctx{ctx_len}]",
            lambda *a: decode_attention(*a, interpret=interp),
            lambda q, ki, ks, vi, vs, n: decode_attention_ref(q, ki, ks, vi, vs, kv_valid_len=n),
            (qd, ki, ks, vi, vs, valid), bf16,
        )
        setup, run = setup + s, run + r

        maxp = ctx_len // page
        pool = slots * maxp + 1  # page 0 is the allocator's scratch page
        kpi, kps = quantize_kv(normal((hkv, pool, page, hd), jnp.float32))
        vpi, vps = quantize_kv(normal((hkv, pool, page, hd), jnp.float32))
        perm = np.random.default_rng(self.args.seed).permutation(np.arange(1, pool))
        tables = jnp.asarray(perm.reshape(slots, maxp), jnp.int32)
        lens = jnp.asarray([ctx_len, ctx_len * 3 // 4, ctx_len // 3, page + 1], jnp.int32)
        s, r = self._kernel_case(
            f"decode_attention[int8,paged,{slots}slots,page{page},ctx{ctx_len}]",
            lambda *a: paged_decode_attention(*a, interpret=interp),
            paged_decode_attention_ref,
            (qd, kpi, kps, vpi, vps, tables, lens), bf16,
        )
        setup, run = setup + s, run + r

        u = normal((1, scan_l, scan_d))
        dt = jax.nn.softplus(normal((1, scan_l, scan_d), jnp.float32)).astype(bf16)
        a = -jnp.exp(normal((scan_d, scan_n), jnp.float32))
        bm, cm = normal((1, scan_l, scan_n)), normal((1, scan_l, scan_n))
        dskip = normal((scan_d,), jnp.float32)
        s, r = self._kernel_case(
            f"selective_scan[l{scan_l},d_inner{scan_d},state{scan_n}]",
            lambda *a: selective_scan(*a, interpret=interp),
            selective_scan_ref, (u, dt, a, bm, cm, dskip), bf16,
        )
        setup, run = setup + s, run + r
        self.report("b.kernels", launches=len(gemms) + 4, setup_s=f"{setup:.3f}",
                    run_s=f"{run:.6f}", interpret=interp)

    # -- c. serving one faulty chip ------------------------------------------

    def serve(self):
        from repro.core import from_fault_map, random_fault_map
        from repro.core.masking import FaultContext
        from repro.data.synthetic import TokenStream
        from repro.kernels.common import dtype_tol
        from repro.models import model as M
        from repro.serve import ContinuousBatchingEngine, Request, ServeEngine

        cfg = self.model_config()
        params, _ = M.init_params(cfg, self.next_key())
        fm = random_fault_map(self.args.seed, cfg.array_rows, cfg.array_cols,
                              SERVE_FAULT_RATE, chip_id="smoke")
        ctx = from_fault_map(fm)  # mode="fap"
        longest = max(p + n for p, n in SERVE_REQUESTS)
        page = 16
        pages_per_seq = -(-longest // page)
        stream = TokenStream(cfg.vocab_size, max(p for p, _ in SERVE_REQUESTS),
                             len(SERVE_REQUESTS), seed=self.args.seed)
        prompts = np.asarray(stream.batch_at(0)["tokens"])
        requests = [
            Request(i, prompts[i, :plen], max_new_tokens=new, arrival=i)
            for i, (plen, new) in enumerate(SERVE_REQUESTS)
        ]

        t0 = time.perf_counter()
        engine = ContinuousBatchingEngine(
            cfg, params, ctx, num_slots=4, page_size=page,
            num_pages=4 * pages_per_seq + 1, max_pages_per_seq=pages_per_seq,
            probe_every=8,
        )
        programs = engine.warmup()
        setup = time.perf_counter() - t0
        t0 = time.perf_counter()
        outs, stats = engine.serve(requests)
        run = time.perf_counter() - t0

        for r in requests:
            o = outs[r.rid]
            check(o.finish_reason == "eos" or len(o.tokens) == r.max_new_tokens,
                  f"request {r.rid} ended with {len(o.tokens)}/{r.max_new_tokens} "
                  f"tokens ({o.finish_reason})")
            check(np.isfinite(o.logprobs).all(), f"request {r.rid}: non-finite logprobs")
        check(stats.chunk_dispatches > 0, "no request took the chunked prefill path")
        counts = engine.compile_counts()
        check(counts["jit_fallback"] == 0,
              f"{counts['jit_fallback']} programs compiled after warmup")
        check(stats.probe_dispatches > 0, "no ABFT probe ran")
        check(engine.health.detections == 0 and engine.health.state(0) == "healthy",
              f"probes flagged the unchanged chip: {engine.health.summary()}")

        # the static engine on the same prompts: one capacity for every
        # request, so it compiles one decode program
        t0 = time.perf_counter()
        ref = ServeEngine(cfg, params, ctx, max_len=self._ladder(longest), page_size=page)
        rtol, atol = dtype_tol(cfg.dtype)
        agree = total = 0
        worst = 0.0
        for r in requests:
            g = ref.generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
            ref_tokens = np.asarray(g.tokens[0, len(r.tokens):])
            # the engines expose no logits: the greedy token's log-probability
            # (max logit minus log-sum-exp) is the logits' summary both report
            lp, ref_lp = float(outs[r.rid].logprobs[0]), float(g.logprobs[0, 0])
            worst = max(worst, abs(lp - ref_lp))
            check(abs(lp - ref_lp) <= atol + rtol * abs(ref_lp),
                  f"request {r.rid}: first-token logprob {lp} vs ServeEngine {ref_lp}")
            agree += int((outs[r.rid].tokens == ref_tokens).sum())
            total += len(ref_tokens)
        ref_s = time.perf_counter() - t0

        # one prefill with the mask fused into the Pallas kernel, against FAP
        batch = {"tokens": jnp.asarray(prompts[:1, :256])}
        pallas_ctx = FaultContext(ok=ctx.ok, mode="pallas")
        prefill = lambda p, b, c: M.prefill(p, b, cfg, c)[0]
        t0 = time.perf_counter()
        fap_fn = jax.jit(prefill).lower(params, batch, ctx).compile()
        pallas_fn = jax.jit(prefill).lower(params, batch, pallas_ctx).compile()
        if not self.rehearse:
            check("tpu_custom_call" in pallas_fn.as_text(),
                  "mode='pallas' prefill holds no Pallas kernel")
        logits_fap = np.asarray(fap_fn(params, batch, ctx), np.float32)
        logits_pallas = np.asarray(pallas_fn(params, batch, pallas_ctx), np.float32)
        pallas_s = time.perf_counter() - t0
        check(np.isfinite(logits_pallas).all(), "pallas prefill: non-finite logits")
        gap = float(np.abs(logits_pallas - logits_fap).max())
        check(np.all(np.abs(logits_pallas - logits_fap) <= atol + rtol * np.abs(logits_fap)),
              f"pallas vs fap prefill logits differ by up to {gap}")

        self.report(
            "c.serve", model=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
            dtype=cfg.dtype, array=f"{cfg.array_rows}x{cfg.array_cols}",
            fault_rate=f"{fm.fault_rate:.4f}", requests=len(requests),
            tokens=stats.emitted_tokens, decode_dispatches=stats.decode_dispatches,
            chunk_dispatches=stats.chunk_dispatches,
            probe_dispatches=stats.probe_dispatches, aot_programs=programs,
            jit_fallback=counts["jit_fallback"], detections=engine.health.detections,
            first_logprob_max_diff=f"{worst:.4g}",
            greedy_agreement=f"{agree}/{total}={agree / total:.3f}",
            pallas_vs_fap_max_diff=f"{gap:.4g}", setup_s=f"{setup:.3f}",
            run_s=f"{run:.3f}", reference_s_with_compile=f"{ref_s:.3f}",
            pallas_check_s_with_compile=f"{pallas_s:.3f}",
        )

    @staticmethod
    def _ladder(n: int) -> int:
        from repro.serve.bucketing import DEFAULT_PREFILL_BUCKETS, ladder_rung

        return ladder_rung(n, DEFAULT_PREFILL_BUCKETS)

    # -- d. eFAT retraining ----------------------------------------------------

    def retrain(self):
        from repro.core import EFAT, EFATConfig, random_fault_map
        from repro.fleet.capacity import suggest_population_size
        from repro.obs import Recorder
        from repro.train.fat_trainer import LMFATTrainer

        cfg = self.model_config()
        pop = suggest_population_size(cfg)
        rec = Recorder()
        t0 = time.perf_counter()
        trainer = LMFATTrainer(
            cfg, seed=self.args.seed, batch_size=8, seq_len=64, pretrain_steps=4,
            eval_every=2, eval_batches=1, population_size=pop,
            engine_kwargs=dict(recorder=rec),
        )
        pretrain_s = time.perf_counter() - t0
        fleet = [
            random_fault_map(self.args.seed + i, cfg.array_rows, cfg.array_cols, rate,
                             chip_id=f"chip{i}")
            for i, rate in enumerate(FLEET_RATES)
        ]
        # an accuracy the few steps here cannot reach: every Step-1 probe
        # and every job then trains to the step cap, so steps really run
        ef = EFAT(trainer, EFATConfig(
            constraint=1.0, max_fr=max(FLEET_RATES), max_interval=0.2,
            step_ratio=4.0, repeats=1, max_steps=4, seed=self.args.seed,
            m_comparisons=2, k_iterations=1,
        ))
        t0 = time.perf_counter()
        table = ef.build_resilience_table(fleet)
        table_s = time.perf_counter() - t0
        check(len(table.rates) == 2, f"resilience table has {len(table.rates)} rates, not 2")
        for name in ("min_steps", "mean_steps", "max_steps_stat"):
            check(np.isfinite(getattr(table, name)).all(), f"table {name} is not finite")

        steps = rec.metrics.counter("train.budget_steps")
        before = steps.value
        t0 = time.perf_counter()
        result = ef.run(fleet)
        plan_setup_s = time.perf_counter() - t0
        executed = steps.value - before
        planned = sum(int(round(s)) for s in result.plan.steps)
        check(planned > 0 and executed == planned,
              f"plan of {planned} steps executed {executed}")
        check(sorted(result.chip_metrics) == list(range(len(fleet))),
              f"per-chip metrics cover chips {sorted(result.chip_metrics)}")
        check(all(math.isfinite(v) for v in result.chip_metrics.values()),
              f"non-finite per-chip metrics {result.chip_metrics}")
        t0 = time.perf_counter()
        again = ef.run(fleet)
        plan_run_s = time.perf_counter() - t0
        check(again.chip_metrics == result.chip_metrics, "a rerun of the plan changed metrics")

        self.report(
            "d.retrain", model=cfg.name, layers=cfg.num_layers,
            population_size=pop, baseline_accuracy=f"{trainer.baseline_metric:.4f}",
            table_rates=[round(float(r), 4) for r in table.rates],
            table_max_steps=[float(s) for s in table.max_steps_stat],
            jobs=result.plan.num_jobs, plan_steps=planned, executed_steps=executed,
            chip_metrics={c: round(v, 4) for c, v in result.chip_metrics.items()},
            setup_s=f"{pretrain_s + table_s + plan_setup_s:.3f}",
            run_s=f"{plan_run_s:.3f}",
        )

    # -- four chips ----------------------------------------------------------

    @staticmethod
    def _devices_of(tree) -> set:
        return {s.device for leaf in jax.tree_util.tree_leaves(tree)
                for s in leaf.addressable_shards}

    def population_mesh(self):
        from repro.core import random_fault_map
        from repro.launch.mesh import make_fleet_mesh
        from repro.train.fat_trainer import LMFATTrainer

        cfg = self.model_config(num_layers=2)
        common = dict(seed=self.args.seed, batch_size=8, seq_len=64, eval_every=1,
                      eval_batches=1, metric="loss", population_size=4)
        t0 = time.perf_counter()
        ref = LMFATTrainer(cfg, pretrain_steps=4, **common)
        maps = [random_fault_map(self.args.seed + i, cfg.array_rows, cfg.array_cols, r)
                for i, r in enumerate((0.05, 0.10, 0.15, 0.20))]
        # recover to the healthy model's loss: members cross at different steps
        constraint = ref.baseline_metric
        max_steps, budgets = 8, [1, 2, 3, 4]
        want_steps = ref.steps_to_constraint_batch(maps, constraint, max_steps)
        want_metrics = ref.evaluate_batch(ref.train_batch(maps, budgets), maps)
        setup = time.perf_counter() - t0

        from repro.kernels.common import dtype_tol

        rtol, atol = dtype_tol(cfg.dtype)
        lines = []
        for pop_ext, model_ext in ((4, 1), (2, 2)):
            t0 = time.perf_counter()
            mesh = make_fleet_mesh(pop_ext, model_ext)
            tr = LMFATTrainer(cfg, pretrain_steps=0, engine="sharded",
                              engine_kwargs=dict(mesh=mesh), **common)
            tr.base_params = ref.base_params
            seen: list = []
            record = tr.engine._record_fit_output

            def spy(trained, keep, width, record=record):
                seen.append(self._devices_of(trained))
                record(trained, keep, width)

            tr.engine._record_fit_output = spy
            got_steps = tr.steps_to_constraint_batch(maps, constraint, max_steps)
            got_metrics = tr.evaluate_batch(tr.train_batch(maps, budgets), maps)
            took = time.perf_counter() - t0
            tag = f"{pop_ext}x{model_ext}"
            check(got_steps == want_steps,
                  f"{tag} steps-to-constraint {got_steps} vs vmap {want_steps}")
            for g, w in zip(got_metrics, want_metrics):
                check(abs(g - w) <= atol + rtol * abs(w),
                      f"{tag} member metrics {got_metrics} vs vmap {want_metrics}")
            check(seen and all(len(s) == 4 for s in seen),
                  f"{tag} fit output spans {[len(s) for s in seen]} devices, not 4")
            lines.append(f"{tag}:steps={got_steps},devices={len(seen[-1])},s={took:.3f}")
        self.report("4.population", model=cfg.name, layers=cfg.num_layers,
                    vmap_steps=want_steps, meshes=" ".join(lines),
                    setup_s_with_compile=f"{setup:.3f}")

    def fleet_serve(self):
        from repro.core import from_fault_map, random_fault_map
        from repro.data.synthetic import TokenStream
        from repro.fleet import ShardedFleetServeEngine
        from repro.kernels.common import dtype_tol
        from repro.models import model as M
        from repro.serve import ContinuousBatchingEngine, Request

        cfg = self.model_config(num_layers=2)
        params, _ = M.init_params(cfg, self.next_key())
        ctxs = [
            from_fault_map(random_fault_map(self.args.seed + c, cfg.array_rows,
                                            cfg.array_cols, 0.05 * (c + 1)))
            for c in range(4)
        ]
        stream = TokenStream(cfg.vocab_size, 300, 12, seed=self.args.seed)
        toks = np.asarray(stream.batch_at(1)["tokens"])
        shapes = ((24, 12, 0), (300, 16, 1), (70, 20, 3))
        streams = [
            [Request(i, toks[3 * c + i, : plen + 8 * c], max_new_tokens=new + c,
                     arrival=arr + c)
             for i, (plen, new, arr) in enumerate(shapes)]
            for c in range(4)
        ]
        kw = dict(num_slots=2, page_size=16, num_pages=2 * 21 + 1, max_pages_per_seq=21)
        t0 = time.perf_counter()
        fleet = ShardedFleetServeEngine(cfg, [params] * 4, ctxs, **kw)
        placed = {name: len(self._devices_of(x))
                  for name, x in (("params", fleet.params), ("fault_masks", fleet.ctx.ok))}
        check(all(n == 4 for n in placed.values()),
              f"chip-stacked fleet state spans {placed} devices, not 4 each")
        outs, stats = fleet.serve(streams)
        fleet_s = time.perf_counter() - t0

        rtol, atol = dtype_tol(cfg.dtype)
        agree = total = 0
        t0 = time.perf_counter()
        for c in range(4):
            want, _ = ContinuousBatchingEngine(cfg, params, ctxs[c], **kw).serve(streams[c])
            for rid, w in want.items():
                g = outs[c][rid]
                check(len(g.tokens) == len(w.tokens),
                      f"chip {c} request {rid}: {len(g.tokens)} vs {len(w.tokens)} tokens")
                check(abs(g.logprobs[0] - w.logprobs[0]) <= atol + rtol * abs(w.logprobs[0]),
                      f"chip {c} request {rid}: first-token logprob "
                      f"{g.logprobs[0]} vs per-chip {w.logprobs[0]}")
                agree += int((g.tokens == w.tokens).sum())
                total += len(w.tokens)
        ref_s = time.perf_counter() - t0
        self.report("4.fleet_serve", model=cfg.name, layers=cfg.num_layers, chips=4,
                    placement=placed, tokens=stats.emitted_tokens,
                    fused_dispatches=stats.decode_dispatches,
                    greedy_agreement=f"{agree}/{total}={agree / total:.3f}",
                    fleet_s_with_compile=f"{fleet_s:.3f}",
                    per_chip_s_with_compile=f"{ref_s:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size, kernels in interpret mode")
    args = ap.parse_args(argv)
    from repro.configs import get_arch

    args.scan_config = get_arch("falcon-mamba-7b")
    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    smoke = Smoke(args)
    try:
        d0 = smoke.device()
        if args.chips == 4:
            smoke.population_mesh()
            smoke.fleet_serve()
        else:
            smoke.kernels()
            smoke.serve()
            smoke.retrain()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:
        traceback.print_exc()
        return 1
    line = {"ok": True, "device": {"platform": d0.platform, "kind": d0.device_kind,
                                   "count": len(jax.devices())}}
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
