"""Serve a whole fleet of faulty chips' deployed models in ONE program.

The deployment half of eFAT at fleet scale, as *request streams*: each chip
runs the fault-aware weights its retraining job shipped, under its own
fault map, and consumes its OWN ragged stream of requests (mixed prompt
lengths, mixed budgets, staggered arrivals) through its own
continuous-batch slot table over a paged KV cache. One
``shard_map``-over-the-pop-mesh dispatch advances every chip's in-flight
slots a token (``ShardedFleetServeEngine``: the continuous engine's own
programs and round loop over a chip axis), so no chip waits on another
chip's traffic — and greedy decoding still reproduces a per-chip
``ContinuousBatchingEngine`` token-for-token.

Force a multi-device CPU mesh to see real sharding:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/fleet_serve.py [--chips 4] \
        [--trace-out fleet.trace.json] [--metrics-out fleet.jsonl]

``--trace-out`` writes a Chrome trace of the fleet run — one Perfetto
swimlane per chip slot plus per-chip page-pool counters; ``--metrics-out``
writes the JSONL event+metrics log (``python -m repro.launch.obs`` converts
or summarizes it). ``--probe-every N`` turns on the online fault-detection
stack (per-chip ABFT checksum/canary probes + health scoring + alerts)
and ``--health-out`` saves the per-chip health summary JSON.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, reduce_config
from repro.core import from_fault_map, healthy, random_fault_map
from repro.core.masking import mask_params
from repro.data.synthetic import TokenStream
from repro.fleet import ShardedFleetServeEngine
from repro.models import model as M
from repro.serve import ContinuousBatchingEngine, Request
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the fleet run's Chrome trace")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the fleet run's JSONL event+metrics log")
    ap.add_argument("--probe-every", type=int, default=None, metavar="N",
                    help="dispatch per-chip ABFT probes every N fused decode "
                         "dispatches and score chip health")
    ap.add_argument("--health-out", default=None, metavar="FILE",
                    help="write the per-chip health + alert summary JSON "
                         "(needs --probe-every)")
    args = ap.parse_args()
    if args.health_out and not args.probe_every:
        ap.error("--health-out needs --probe-every")

    cfg = reduce_config(get_arch("qwen3-0.6b"))
    stream = TokenStream(cfg.vocab_size, 32, 8, seed=2, noise=0.02)
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    ocfg = AdamWConfig(learning_rate=3e-3)
    train = jax.jit(make_train_step(cfg, ocfg, remat="none"))

    opt = adamw_init(params, ocfg)
    for i in range(100):
        params, opt, _ = train(params, opt, stream.batch_at(i), healthy())

    # one quick FAT pass per chip, shipping FAP-masked weights (chip 0 stays
    # healthy to show mixed fleets)
    chips = []
    for c in range(args.chips):
        if c == 0:
            chips.append((params, healthy(), 0.0))
            continue
        fm = random_fault_map(c, cfg.array_rows, cfg.array_cols, 0.1 + 0.05 * c,
                              chip_id=f"edge-{c}")
        ctx = from_fault_map(fm)
        p, o = params, adamw_init(params, ocfg)
        for i in range(30):
            p, o, _ = train(p, o, stream.batch_at(500 + i), ctx)
        chips.append((mask_params(p, ctx), ctx, fm.fault_rate))

    # each chip gets its OWN traffic: different lengths, budgets, arrivals
    def stream_for(c: int) -> list[Request]:
        tok = lambda i, n: np.asarray(stream.batch_at(60 + 10 * c + i)["tokens"][0, :n])
        return [
            Request(0, tok(0, 8 + 2 * c), max_new_tokens=4 + 3 * c),
            Request(1, tok(1, 12), max_new_tokens=16 - 2 * c),
            Request(2, tok(2, 6), max_new_tokens=6, arrival=2 + c),
            Request(3, tok(3, 10), max_new_tokens=8, arrival=4),
        ]

    streams = [stream_for(c) for c in range(args.chips)]

    rec = None
    if args.trace_out or args.metrics_out or args.health_out:
        from repro.obs import Recorder

        rec = Recorder()
    alert_rules = None
    if args.probe_every:
        from repro.obs import default_slo_rules

        alert_rules = default_slo_rules()
    t0 = time.time()
    fleet_eng = ShardedFleetServeEngine(
        cfg, [p for p, _, _ in chips], [c for _, c, _ in chips],
        num_slots=2, page_size=8, num_pages=64, recorder=rec,
        probe_every=args.probe_every, alert_rules=alert_rules,
    )
    aot = fleet_eng.warmup()
    outs, stats = fleet_eng.serve(streams)
    t_fleet = time.time() - t0
    assert fleet_eng.compile_counts()["jit_fallback"] == 0
    print(
        f"fleet engine: {len(chips)} chips (pop mesh extent "
        f"{int(fleet_eng.mesh.shape['pop'])}) served "
        f"{stats.emitted_tokens} tokens across {stats.admitted} ragged requests "
        f"in {stats.decode_dispatches} fused dispatches / {t_fleet:.2f}s "
        f"(slot utilization {stats.slot_utilization:.0%}; {aot} programs "
        f"compiled ahead of traffic, none during it)"
    )

    t0 = time.time()
    per_chip_dispatches = 0
    for c, (p, ctx, _) in enumerate(chips):
        ref, ref_stats = ContinuousBatchingEngine(
            cfg, p, ctx, num_slots=2, page_size=8, num_pages=64
        ).serve(streams[c])
        per_chip_dispatches += ref_stats.decode_dispatches
        for rid, out in ref.items():
            assert np.array_equal(outs[c][rid].tokens, out.tokens), (c, rid)
    t_serial = time.time() - t0
    print(
        f"per-chip engines (reference): {per_chip_dispatches} dispatches / "
        f"{t_serial:.2f}s — fleet output matches token-for-token; "
        f"{per_chip_dispatches / stats.decode_dispatches:.2f}x dispatch amortization"
    )

    for c, (_, _, rate) in enumerate(chips):
        o = outs[c]
        lead = o[0]
        health = (
            f" health={fleet_eng.health.state(c)}"
            if fleet_eng.health is not None else ""
        )
        print(
            f"  chip {c}: fault_rate={rate:.2f} requests={len(o)} "
            f"ttft(rid0)={lead.ttft} continuation={lead.tokens.tolist()}{health}"
        )
    if args.probe_every:
        print(
            f"probes: {stats.probe_dispatches} dispatches "
            f"(every {args.probe_every} fused steps), detections="
            f"{fleet_eng.health.detections}, alerts firing="
            f"{fleet_eng.alerts.firing() if fleet_eng.alerts else []}"
        )
    if args.health_out:
        import json

        with open(args.health_out, "w") as f:
            json.dump(dict(
                health=fleet_eng.health.summary(),
                alerts=fleet_eng.alerts.summary() if fleet_eng.alerts else None,
            ), f, indent=2)
        print(f"health: {args.health_out}")

    if args.trace_out:
        from repro.obs import write_chrome_trace

        tr = write_chrome_trace(args.trace_out, rec)
        print(f"trace: {args.trace_out} ({len(tr['traceEvents'])} events — "
              f"one Perfetto lane per chip slot)")
    if args.metrics_out:
        from repro.obs import write_jsonl

        write_jsonl(args.metrics_out, rec)
        print(f"metrics: {args.metrics_out} ({len(rec.event_list())} events, "
              f"recorder self time {rec.self_time_s*1e3:.2f} ms)")


if __name__ == "__main__":
    main()
