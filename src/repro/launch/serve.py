"""Serving launcher CLI: batched generation with an optional fault map.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --fault-rate 0.1 --batch 4 --new-tokens 16

``--continuous`` serves the same prompts as a request stream through the
continuous-batching engine (paged KV cache, per-request budgets skewed
around --new-tokens) instead of one static batch. With ``--trace-out`` /
``--metrics-out`` the continuous run records its request lifecycle
(repro.obs) and writes a Chrome trace / JSONL event+metrics log; convert
or summarize saved logs with ``python -m repro.launch.obs``.
``--probe-every N`` turns on the online fault-detection stack (ABFT
checksum/canary probes + health scoring + SLO alerts) and
``--health-out`` saves its summary JSON.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--fault-rate", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (paged KV, skewed budgets)")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--buckets", type=int, nargs="+", default=None, metavar="W",
                    help="prefill bucket ladder (default 32 64 128 256); "
                         "pass 0 to disable bucketing (exact-length prefill)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="chunked-prefill width for prompts past the top "
                         "bucket (default: the top bucket)")
    ap.add_argument("--max-pack", type=int, default=4,
                    help="max short prompts packed into one bucket dispatch")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-precompile every (bucket, chunk, decode) "
                         "program before serving (continuous engine only)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the run's Chrome trace (continuous only)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the run's JSONL event+metrics log "
                         "(continuous only)")
    ap.add_argument("--probe-every", type=int, default=None, metavar="N",
                    help="dispatch an ABFT checksum/canary probe every N "
                         "decode dispatches and score chip health "
                         "(continuous only)")
    ap.add_argument("--health-out", default=None, metavar="FILE",
                    help="write the health + alert summary JSON "
                         "(needs --probe-every)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.health_out and not args.probe_every:
        ap.error("--health-out needs --probe-every")

    import jax

    from repro.configs import get_arch, reduce_config
    from repro.core import from_fault_map, healthy, random_fault_map
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if cfg.is_encoder:
        raise SystemExit("encoder-only arch has no decode path")
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    ctx = healthy()
    if args.fault_rate > 0:
        fm = random_fault_map(0, cfg.array_rows, cfg.array_cols, args.fault_rate)
        ctx = from_fault_map(fm)
        print(f"fault map rate={fm.fault_rate:.3f}")

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    if args.continuous:
        import numpy as np

        from repro.serve import ContinuousBatchingEngine, Request

        budgets = [
            max(1, args.new_tokens // (4 if i % 2 else 1)) for i in range(args.batch)
        ]
        reqs = [
            Request(i, np.asarray(prompts[i]), max_new_tokens=budgets[i], arrival=i % 3)
            for i in range(args.batch)
        ]
        from repro.serve.bucketing import DEFAULT_PREFILL_BUCKETS

        buckets = (
            None
            if args.buckets == [0]
            else tuple(args.buckets) if args.buckets else DEFAULT_PREFILL_BUCKETS
        )
        rec = None
        if args.trace_out or args.metrics_out or args.health_out:
            from repro.obs import Recorder

            rec = Recorder()
        alert_rules = None
        if args.probe_every:
            from repro.obs import default_slo_rules

            alert_rules = default_slo_rules()
        eng = ContinuousBatchingEngine(
            cfg, params, ctx, num_slots=args.slots, prefill_buckets=buckets,
            chunk_size=args.chunk_size, max_pack=args.max_pack, recorder=rec,
            probe_every=args.probe_every, alert_rules=alert_rules,
        )
        if args.warmup:
            t0 = time.time()
            n = eng.warmup()
            print(f"warmup: {n} AOT programs in {time.time() - t0:.2f}s")
        t0 = time.time()
        outs, stats = eng.serve(reqs, temperature=args.temperature)
        dt = time.time() - t0
        cc = eng.compile_counts()
        print(
            f"{stats.emitted_tokens} tokens over {args.batch} requests in "
            f"{stats.decode_dispatches} dispatches / {dt:.2f}s "
            f"({stats.emitted_tokens/dt:.1f} tok/s, "
            f"slot util {stats.slot_utilization:.0%}, "
            f"peak KV {stats.peak_resident_kv_bytes} B, "
            f"compiles aot={cc['aot']} jit={cc['jit_fallback']})"
        )
        for i in range(min(2, args.batch)):
            o = outs[i]
            print(f"req{i}: ttft={o.ttft} qwait={o.queue_wait_steps} {o.tokens.tolist()}")
        if args.probe_every:
            print(
                f"probes: {stats.probe_dispatches} dispatches "
                f"(every {args.probe_every}), health={eng.health.state(0)}, "
                f"alerts firing={eng.alerts.firing() if eng.alerts else []}"
            )
        if args.health_out:
            import json

            with open(args.health_out, "w") as f:
                json.dump(dict(
                    health=eng.health.summary(),
                    alerts=eng.alerts.summary() if eng.alerts else None,
                ), f, indent=2)
            print(f"health: {args.health_out}")
        if args.trace_out:
            from repro.obs import write_chrome_trace

            t = write_chrome_trace(args.trace_out, rec)
            print(f"trace: {args.trace_out} ({len(t['traceEvents'])} events)")
        if args.metrics_out:
            from repro.obs import write_jsonl

            write_jsonl(args.metrics_out, rec)
            print(f"metrics: {args.metrics_out} "
                  f"({len(rec.event_list())} events, "
                  f"self time {rec.self_time_s*1e3:.2f} ms)")
        return

    engine = ServeEngine(cfg, params, ctx, max_len=args.max_len)
    t0 = time.time()
    out = engine.generate(
        prompts, max_new_tokens=args.new_tokens, temperature=args.temperature
    )
    dt = time.time() - t0
    print(f"{args.batch}x{args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch*args.new_tokens/dt:.1f} tok/s)")
    for i in range(min(2, args.batch)):
        print(f"seq{i}: {out.tokens[i, args.prompt_len:].tolist()}")


if __name__ == "__main__":
    main()
