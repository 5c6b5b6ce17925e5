"""Serving harness: static batching vs continuous batching on a skewed trace.

The static engine (``ServeEngine``) drains FCFS batches of ``num_slots``
requests to the LONGEST request's horizon — a request that finishes at
token 5 burns a dispatch per token until its batchmates finish, and every
sequence holds a dense KV buffer for the whole batch. The continuous engine
(``ContinuousBatchingEngine``) retires each request at its own budget and
frees its pages immediately, so a waiting request refills the slot
mid-flight.

Both engines serve the SAME skewed-generation-length trace with the same
greedy math, and the harness verifies on the way that per-request tokens
are identical — the savings are only real if the outputs are unchanged.
The run FAILS (exit 1) unless continuous batching strictly reduces BOTH
total decode dispatches and peak resident KV bytes.

``--fleet`` adds the sharded tier: N chips' independent ragged streams
through ``ShardedFleetServeEngine`` (shard_map over the pop mesh — force
host devices via XLA_FLAGS, as the CI serve job does), re-verifying that
per-chip outputs match per-chip continuous engines and that fused fleet
dispatches stay at busiest-chip scale rather than fleet-sum scale.

``--heavy-traffic`` adds the production-shaped admission benchmark: a
Poisson-arrival, Zipfian-prompt-length request stream served through the
continuous engine — once UNBUCKETED (exact-length prefill: one compiled
program per distinct prompt length, the `RCP001` hazard) and once through
the bucketed/packed/chunked planner with AOT warmup. Both runs share one
BOUNDED page pool (admission backpressure via ``PageAllocator.can_alloc``
— queue-wait is reported alongside TTFT). The run FAILS unless the
bucketed run's greedy tokens match the unbucketed run's (and a sampled
subset matches per-request ``ServeEngine``), its prefill program count is
O(|buckets|) and equals the planner-census prediction, zero jit compiles
happen after warmup, and its p99 wall-clock TTFT beats the unbucketed run.

Every instrumented run carries a ``repro.obs`` :class:`Recorder`: the
TTFT / queue-wait / TPOT percentiles in the report come from its
histograms (the same aggregates production would scrape), not from ad-hoc
arrays. ``--heavy-traffic`` additionally serves the bucketed trace
recorder-OFF and gates the observability overhead: recorder-on throughput
must stay within ``OBS_OVERHEAD_FLOOR`` of recorder-off (one re-run is
allowed to damp wall-clock flake) and the sampled tokens must be BITWISE
identical — instrumentation is host-side only and may not touch the math.
``--trace-out FILE`` exports the recorded spans (serve + fleet) as a
schema-validated Chrome trace viewable in https://ui.perfetto.dev.

``--inject-fault`` adds the online fault-DETECTION benchmark (ROADMAP
item 2): a fleet serves with the ABFT checksum-probe / health-scoring /
alert stack on, one chip's silicon changes mid-serve under the engine, and
the run FAILS unless the victim chip is detected within a bounded number
of decode dispatches with a correctly localized fault delta, zero false
positives anywhere else (including a probed control run with no
injection), a fired detection alert in the trace, and bitwise-unchanged
tokens on every healthy chip. The recorder-on heavy-traffic arm also
carries probes, so the overhead/parity gates cover the detection stack.
``--health-out FILE`` writes the per-chip health + alert summary JSON.

Output is JSON (tokens/sec, time-to-first-token in dispatches, slot
utilization, resident KV bytes) so CI can parse it; ``--smoke`` shrinks the
trace to CI scale. ``--out`` with no value writes the canonical in-tree
snapshot ``benchmarks/BENCH_serve.json``.

Usage:
    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--fleet]
        [--heavy-traffic] [--inject-fault] [--health-out FILE]
        [--trace-out FILE] [--out [FILE]]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

CANONICAL_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_serve.json")

# recorder-on throughput must stay within this fraction of recorder-off
OBS_OVERHEAD_FLOOR = 0.95


def _obs_percentiles(recorder, wall: float) -> dict:
    """Latency percentiles read off the recorder's histograms — the single
    computation path the report and production scrapes share."""
    m = recorder.summary()["metrics"]

    def pct(name, q):
        h = m.get(name)
        return h[f"p{q}"] if h else None

    return dict(
        ttft_wall_p50_s=pct("serve.ttft_wall_s", 50),
        ttft_wall_p99_s=pct("serve.ttft_wall_s", 99),
        queue_wait_p50_steps=pct("serve.queue_wait_steps", 50),
        queue_wait_p99_steps=pct("serve.queue_wait_steps", 99),
        tpot_p50_s=pct("serve.tpot_s", 50),
        tpot_p99_s=pct("serve.tpot_s", 99),
        obs=dict(
            events=recorder.summary()["events"],
            events_dropped=recorder.events.dropped,
            self_time_s=recorder.self_time_s,
            self_time_fraction=recorder.self_time_s / wall if wall else 0.0,
        ),
    )


def _trace_complete(recorder, rids: set, *, chunked_traffic: bool) -> bool:
    """Every retired request must carry its full lifecycle in the trace:
    an admit span (packed/bucketed) or chunk spans (chunked), a decode
    span and a retire instant — plus page-pool counter samples."""
    evs = recorder.event_list()
    by = lambda n: {e.args["rid"] for e in evs if e.name == n and e.args}  # noqa: E731
    admit, chunk, decode, retire = by("admit"), by("chunk"), by("decode"), by("retire")
    pages_sampled = any(
        e.kind == "sample" and e.name.endswith("free_pages") for e in evs
    )
    return (
        decode == rids
        and retire == rids
        and (admit | chunk) == rids
        and bool(chunk) == chunked_traffic
        and pages_sampled
    )


def build_trace(cfg, *, smoke: bool):
    """Skewed-length request trace: rectangular prompts (so the static
    engine can batch them at all), budgets spanning ~10x."""
    import jax
    import numpy as np

    from repro.serve import Request

    # long/short interleaved (the arrival pattern FCFS batching suffers on:
    # every static batch inherits its longest member's horizon)
    budgets = [4, 24, 4, 12, 6, 16, 6, 8] if smoke else [
        4, 64, 4, 24, 6, 32, 8, 48, 6, 12, 8, 16, 12, 4,
    ]
    plen = 8
    key = jax.random.PRNGKey(42)
    reqs = []
    for i, b in enumerate(budgets):
        toks = np.asarray(
            jax.random.randint(jax.random.fold_in(key, i), (plen,), 0, cfg.vocab_size)
        )
        reqs.append(Request(i, toks, max_new_tokens=b))
    return reqs, plen


def run_static(cfg, params, trace, plen, *, num_slots, page_size):
    """FCFS static batching: batches of ``num_slots``, each run to its
    longest member's horizon, per-request tokens truncated to own budget."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import ServeEngine, dense_kv_bytes

    eng = ServeEngine(cfg, params, max_len=None, page_size=page_size)
    compiled_fns = (eng._prefill_len, eng._sample_decode)
    outputs = {}
    dispatches = 0
    peak_bytes = 0
    byte_steps = 0
    emitted = 0
    wasted = 0  # slot-steps burned past a request's own budget
    ttft = {}
    t0 = time.time()
    for lo in range(0, len(trace), num_slots):
        batch = trace[lo : lo + num_slots]
        horizon = max(r.max_new_tokens for r in batch)
        prompts = jnp.stack([jnp.asarray(r.tokens) for r in batch])
        out = eng.generate(prompts, max_new_tokens=horizon)
        cache_len = eng.cache_len_for(plen, horizon)
        batch_bytes = dense_kv_bytes(cfg, len(batch), cache_len)
        peak_bytes = max(peak_bytes, batch_bytes)
        byte_steps += horizon * batch_bytes
        for j, r in enumerate(batch):
            outputs[r.rid] = np.asarray(out.tokens[j, plen : plen + r.max_new_tokens])
            ttft[r.rid] = dispatches + 1
            emitted += r.max_new_tokens
            wasted += horizon - r.max_new_tokens
        dispatches += horizon
    wall = time.time() - t0
    return outputs, dict(
        decode_dispatches=dispatches,
        emitted_tokens=emitted,
        wasted_slot_steps=wasted,
        peak_resident_kv_bytes=peak_bytes,
        kv_byte_steps=byte_steps,
        mean_ttft_dispatches=float(np.mean(list(ttft.values()))),
        # real compile count over the run: the jit caches of the engine's
        # prefill + fused decode (the recompile census predicts these)
        compiles=sum(f._cache_size() for f in compiled_fns),
        wall_s=wall,
        tokens_per_s=emitted / wall if wall else float("inf"),
    )


def run_continuous(cfg, params, trace, *, num_slots, page_size, num_pages):
    import numpy as np

    from repro.obs import Recorder
    from repro.serve import ContinuousBatchingEngine

    rec = Recorder()
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=num_slots, page_size=page_size,
        num_pages=num_pages, recorder=rec,
    )
    t0 = time.time()
    outs, stats = eng.serve(trace)
    wall = time.time() - t0
    d = stats.as_dict()
    d.update(
        mean_ttft_dispatches=float(np.mean([o.ttft for o in outs.values()])),
        compiles=eng.compile_counts()["total"],
        wall_s=wall,
        tokens_per_s=stats.emitted_tokens / wall if wall else float("inf"),
        **_obs_percentiles(rec, wall),
    )
    return {r: o.tokens for r, o in outs.items()}, d, rec


def run_fleet(cfg, params, trace, *, chips, num_slots, page_size, num_pages):
    """Sharded ragged fleet serving vs per-chip continuous engines."""
    import numpy as np

    from repro.core import from_fault_map, healthy, random_fault_map
    from repro.fleet import ShardedFleetServeEngine
    from repro.obs import Recorder
    from repro.serve import ContinuousBatchingEngine, Request

    ctxs = [healthy()] + [
        from_fault_map(random_fault_map(c, cfg.array_rows, cfg.array_cols, 0.1 + 0.05 * c))
        for c in range(1, chips)
    ]
    # ragged: chip c serves a rotated slice of the trace (different budgets)
    streams = []
    for c in range(chips):
        rot = trace[c:] + trace[:c]
        streams.append([
            Request(r.rid, r.tokens, r.max_new_tokens, arrival=(i % 3))
            for i, r in enumerate(rot[: max(3, len(trace) // 2)])
        ])
    rec = Recorder()
    eng = ShardedFleetServeEngine(
        cfg, [params] * chips, ctxs,
        num_slots=num_slots, page_size=page_size, num_pages=num_pages,
        recorder=rec,
    )
    t0 = time.time()
    outs, stats = eng.serve(streams)
    wall = time.time() - t0
    pinned = True
    per_chip_dispatches = 0
    for c in range(chips):
        ref_eng = ContinuousBatchingEngine(
            cfg, params, ctxs[c],
            num_slots=num_slots, page_size=page_size, num_pages=num_pages,
        )
        ref, ref_stats = ref_eng.serve(streams[c])
        per_chip_dispatches += ref_stats.decode_dispatches
        for rid in ref:
            if not np.array_equal(outs[c][rid].tokens, ref[rid].tokens):
                pinned = False
    d = stats.as_dict()
    d.update(
        chips=chips,
        mesh_extent=int(eng.mesh.shape[eng.axis_name]),
        pinned_vs_per_chip_engines=pinned,
        per_chip_engine_dispatches_total=per_chip_dispatches,
        fused_dispatch_amortization=(
            per_chip_dispatches / stats.decode_dispatches
            if stats.decode_dispatches else float("inf")
        ),
        wall_s=wall,
        **_obs_percentiles(rec, wall),
    )
    # per-chip track census: Perfetto should draw one lane per chip slot
    d["obs"]["chip_tracks"] = sorted(
        {e.track for e in rec.event_list() if e.track.startswith("chip")}
    )
    return d, rec


def build_heavy_trace(cfg, *, smoke: bool, buckets):
    """Poisson arrivals, Zipfian prompt lengths: many short prompts, a heavy
    tail of distinct lengths, and a slice past the top bucket so the
    chunked path carries real traffic."""
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(1234)
    n = 48 if smoke else 400
    top = buckets[-1]
    lens = np.clip(rng.zipf(1.3, size=n), 1, top + top // 2).astype(int)
    # guarantee chunked traffic regardless of the zipf draw
    lens[:: max(1, n // 6)] = rng.integers(top + 1, top + top // 2, size=len(lens[:: max(1, n // 6)]))
    budgets = rng.integers(4, 13 if smoke else 33, size=n)
    arrivals = np.cumsum(rng.poisson(1.0, size=n))
    reqs = []
    for i in range(n):
        toks = np.asarray(rng.integers(0, cfg.vocab_size, size=int(lens[i])))
        reqs.append(Request(i, toks, max_new_tokens=int(budgets[i]),
                            arrival=int(arrivals[i])))
    return reqs


def run_heavy(cfg, params, trace, *, num_slots, page_size, num_pages,
              max_pages_per_seq, buckets, warmup, recorder=None,
              probe_every=None, alert_rules=None):
    """One heavy-traffic serve: bucketed planner when ``buckets`` is set
    (AOT-warmed when ``warmup``), exact-length admission when None. Latency
    percentiles are recorder-derived; a ``recorder=None`` run reports raw
    throughput only (the overhead baseline). ``probe_every`` turns the ABFT
    probe/health/alert stack on — the zero-token-impact gate then covers
    probes too."""
    from repro.serve import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=num_slots, page_size=page_size,
        num_pages=num_pages, max_pages_per_seq=max_pages_per_seq,
        prefill_buckets=buckets, recorder=recorder,
        probe_every=probe_every, alert_rules=alert_rules,
    )
    warm_s = 0.0
    if warmup:
        t0 = time.time()
        eng.warmup()
        warm_s = time.time() - t0
    t0 = time.time()
    outs, stats = eng.serve(trace)
    wall = time.time() - t0
    d = stats.as_dict()
    d.update(
        warmup_s=warm_s,
        wall_s=wall,
        tokens_per_s=stats.emitted_tokens / wall if wall else float("inf"),
        compiles=eng.compile_counts(),
    )
    if recorder is not None:
        d.update(_obs_percentiles(recorder, wall))
    return {r: o.tokens for r, o in outs.items()}, d, eng


def run_heavy_traffic(cfg, params, *, smoke, num_slots, page_size):
    """The bucketed-vs-unbucketed admission benchmark (see module doc)."""
    import numpy as np

    from repro.obs import (
        HEALTHY,
        Recorder,
        chrome_trace,
        detection_rules,
        validate_chrome_trace,
    )
    from repro.serve import ServeEngine, pages_needed
    from repro.serve.bucketing import DEFAULT_PREFILL_BUCKETS, bucket_of

    buckets = DEFAULT_PREFILL_BUCKETS
    probe_every = 8  # the recorder-on arm carries the full detection stack
    trace = build_heavy_trace(cfg, smoke=smoke, buckets=buckets)
    # BOUNDED pool: room for num_slots maximal requests, NOT the whole
    # trace at once — admission waits on PageAllocator.can_alloc and the
    # queue-wait percentiles below measure that backpressure
    max_pages_per_seq = max(
        pages_needed(len(r.tokens) + r.max_new_tokens, page_size) for r in trace
    )
    num_pages = 1 + num_slots * max_pages_per_seq
    kw = dict(num_slots=num_slots, page_size=page_size, num_pages=num_pages,
              max_pages_per_seq=max_pages_per_seq)

    un_rec = Recorder()
    un_out, un, _ = run_heavy(cfg, params, trace, buckets=None, warmup=False,
                              recorder=un_rec, **kw)

    # observability overhead gate: bucketed trace recorder-OFF vs recorder-ON.
    # Throughput on a shared CI box flakes (single-arm wall clock swings
    # ~10% run to run), so a below-floor attempt earns re-runs of both
    # arms (best ratio kept, up to three attempts); tokens must be bitwise
    # identical always.
    best = None
    attempts = 0
    for _ in range(3):
        attempts += 1
        # BOTH arms carry the probe stack so the ratio isolates recorder
        # cost (the PR-8 overhead budget); probe zero-token-impact is
        # separately pinned by heavy_tokens_match_unbucketed — the
        # unbucketed arm runs probe-free and must agree bitwise
        off_out, off, _ = run_heavy(cfg, params, trace, buckets=buckets,
                                    warmup=True, recorder=None,
                                    probe_every=probe_every, **kw)
        rec = Recorder()
        bk_out, bk, eng = run_heavy(cfg, params, trace, buckets=buckets,
                                    warmup=True, recorder=rec,
                                    probe_every=probe_every,
                                    alert_rules=detection_rules(), **kw)
        ratio = (bk["tokens_per_s"] / off["tokens_per_s"]
                 if off["tokens_per_s"] else 0.0)
        if best is None or ratio > best[0]:
            best = (ratio, off_out, off, bk_out, bk, eng, rec)
        if ratio >= OBS_OVERHEAD_FLOOR:
            break
    ratio, off_out, off, bk_out, bk, eng, rec = best

    obs_parity = set(off_out) == set(bk_out) and all(
        np.array_equal(off_out[r], bk_out[r]) for r in off_out
    )
    trace_obj = chrome_trace(rec)  # the bucketed production-path recording
    trace_problems = validate_chrome_trace(trace_obj)
    chunked_rids = {r.rid for r in trace
                    if bucket_of(len(r.tokens), buckets) is None}

    # planner census: the CLOSED program set — the same signature model the
    # static analyzer's recompile pass uses for this entry. Packing may
    # merge prompts into a larger bucket than any one of them needs, so the
    # census is the full ladder, not the per-request buckets.
    predicted = {("prefill_admit", b) for b in buckets}
    predicted |= {("prefill_chunk", eng.chunk_size), ("decode",)}
    chunked_traffic = any(bucket_of(len(r.tokens), buckets) is None for r in trace)
    tokens_match = set(un_out) == set(bk_out) and all(
        np.array_equal(un_out[r], bk_out[r]) for r in un_out
    )
    # per-request ServeEngine reference on a length-spread sample (the full
    # trace would re-run the model once per request)
    sample = sorted(trace, key=lambda r: len(r.tokens))
    sample = sample[:: max(1, len(sample) // 8)]
    ref = ServeEngine(cfg, params, max_len=None, page_size=page_size)
    serve_match = True
    for r in sample:
        import jax.numpy as jnp

        res = ref.generate(jnp.asarray(r.tokens)[None],
                           max_new_tokens=r.max_new_tokens)
        want = np.asarray(res.tokens[0, len(r.tokens):])
        serve_match &= np.array_equal(want, bk_out[r.rid])
    checks = dict(
        heavy_tokens_match_unbucketed=bool(tokens_match),
        heavy_tokens_match_serve_engine=bool(serve_match),
        # O(|buckets|): the whole run compiles at most one program per
        # bucket + the chunk program + decode, vs one per distinct length
        heavy_compile_bounded=bk["compiles"]["total"] <= len(buckets) + 2,
        heavy_zero_jit_after_warmup=bk["compiles"]["jit_fallback"] == 0,
        # measured compiles land exactly on the census set, and every
        # program actually dispatched is one the census predicts
        heavy_census_match=(
            bk["compiles"]["total"] == len(predicted)
            and set(eng.used_programs) <= predicted
            and ("decode",) in eng.used_programs
            and (("prefill_chunk", eng.chunk_size) in eng.used_programs)
            == chunked_traffic
        ),
        heavy_p99_ttft_reduced=bk["ttft_wall_p99_s"] < un["ttft_wall_p99_s"],
        # observability gates: host-side hooks change zero tokens, cost
        # under (1 - OBS_OVERHEAD_FLOOR) of throughput, and the exported
        # trace is schema-valid and lifecycle-complete
        heavy_obs_zero_token_impact=bool(obs_parity),
        heavy_obs_overhead_ok=ratio >= OBS_OVERHEAD_FLOOR,
        heavy_trace_valid=not trace_problems,
        heavy_trace_complete=_trace_complete(
            rec, set(bk_out), chunked_traffic=bool(chunked_rids)
        ),
        # detection gates on a HEALTHY run: the probe/health/alert stack
        # rode along the whole recorder-on serve and must stay silent —
        # golden-snapshot probing makes false positives a structural bug
        heavy_probe_zero_false_positives=(
            eng.health is not None
            and eng.health.detections == 0
            and eng.health.state(0) == HEALTHY
        ),
        heavy_alerts_quiet=eng.alerts is not None and eng.alerts.fired_total == 0,
    )
    report = dict(
        requests=len(trace),
        distinct_prompt_lens=len({len(r.tokens) for r in trace}),
        buckets=list(buckets),
        chunk_size=eng.chunk_size,
        num_pages=num_pages,
        max_pages_per_seq=max_pages_per_seq,
        serve_engine_sample=len(sample),
        predicted_programs=sorted(map(str, predicted)),
        used_programs=sorted(map(str, eng.used_programs)),
        unbucketed=un,
        bucketed=bk,
        overhead=dict(
            floor=OBS_OVERHEAD_FLOOR,
            attempts=attempts,
            tokens_per_s_recorder_off=off["tokens_per_s"],
            tokens_per_s_recorder_on=bk["tokens_per_s"],
            throughput_ratio=ratio,
            recorder_self_time_fraction=bk["obs"]["self_time_fraction"],
            trace_problems=trace_problems,
        ),
        detection=dict(
            probe_every=probe_every,
            probe_dispatches=bk.get("probe_dispatches", 0),
            health=eng.health.summary() if eng.health else None,
            alerts=eng.alerts.summary() if eng.alerts else None,
        ),
        checks=checks,
    )
    return report, checks, rec


def run_inject_fault(cfg, params, *, smoke, chips, num_slots, page_size):
    """Mid-serve fault-injection detection benchmark (ROADMAP item 2).

    A fleet of ``chips`` chips — every one constructed with an ACTIVE
    (possibly zero-fault) FaultMap context so the stacked ok mask is a live
    program input — serves ragged streams with the ABFT probe / health /
    alert stack on. Mid-serve, one chip's silicon changes under the engine
    (``set_silicon``: new faults appear beyond the believed map). Gates:

    * the victim chip leaves ``healthy`` within a bounded number of decode
      dispatches of the injection (probe cadence x debounce);
    * the reconstructed fault delta is nonempty and a subset of the TRUE
      newly-faulty PEs (syndrome localization, not just divergence);
    * no other chip transitions (zero cross-chip false positives) and a
      control run without injection detects nothing at all;
    * the detection alert fires into the recorder (Perfetto lane);
    * every non-victim chip's tokens are bitwise identical to the control
      run — detection rides along without touching healthy chips' math.
    """
    import numpy as np

    from repro.core import from_fault_map, random_fault_map
    from repro.core.faults import FaultMap
    from repro.fleet import ShardedFleetServeEngine
    from repro.obs import HEALTHY, Recorder, detection_rules
    from repro.obs.health import HealthConfig
    from repro.serve import Request

    R, C = cfg.array_rows, cfg.array_cols
    victim = 1 if chips > 1 else 0
    probe_every = 4
    hc = HealthConfig()
    # believed silicon at engine build: chip 0 pristine, the rest lightly
    # faulty (their FAP masks absorb those) — all ACTIVE contexts
    base_maps = [FaultMap(faulty=np.zeros((R, C), bool))] + [
        random_fault_map(c, R, C, 0.04 + 0.02 * c) for c in range(1, chips)
    ]
    extra = random_fault_map(999, R, C, 0.05)
    new_map = base_maps[victim].merge(extra)
    true_delta = new_map.faulty & ~base_maps[victim].faulty
    assert true_delta.any(), "injection must add at least one new fault"

    trace, _ = build_trace(cfg, smoke=smoke)
    streams = []
    for c in range(chips):
        rot = trace[c:] + trace[:c]
        streams.append([
            Request(r.rid, r.tokens, max_new_tokens=max(r.max_new_tokens, 16),
                    arrival=(i % 3))
            for i, r in enumerate(rot[: max(3, len(trace) // 2)])
        ])

    def build(recorder):
        return ShardedFleetServeEngine(
            cfg, [params] * chips, [from_fault_map(m) for m in base_maps],
            num_slots=num_slots, page_size=page_size,
            num_pages=1 + num_slots * 16,
            recorder=recorder, probe_every=probe_every, health_config=hc,
            alert_rules=detection_rules(),
        )

    # control arm: identical fleet, probes on, nothing injected — the
    # healthy-fleet zero-false-positive gate and the token baseline
    ctl_eng = build(None)
    ctl_outs, _ = ctl_eng.serve([list(s) for s in streams])

    rec = Recorder()
    eng = build(rec)
    inject_clock = probe_every + 2  # after the first probe tick validated
    injected = {}

    def on_step(clock):
        if clock >= inject_clock and not injected:
            injected["at"] = clock
            eng.set_silicon(victim, from_fault_map(new_map))

    t0 = time.time()
    outs, stats = eng.serve([list(s) for s in streams], on_step=on_step)
    wall = time.time() - t0

    detected_at = eng.health.detected_at(victim)
    latency = (detected_at - injected["at"]) if detected_at is not None else None
    # cadence x debounce: one probe tick to first divergence, suspect_after
    # consecutive bad probes to transition, +1 tick of scheduling slack
    latency_bound = probe_every * (hc.suspect_after + 1)
    delta = eng.health.last_delta(victim)
    others_pinned = all(
        np.array_equal(outs[c][rid].tokens, ctl_outs[c][rid].tokens)
        for c in range(chips) if c != victim for rid in ctl_outs[c]
    )
    alert_names = {e.name for e in rec.event_list() if e.kind == "instant"
                   and e.name == "alert"}
    checks = dict(
        inject_detected=eng.health.state(victim) != HEALTHY,
        inject_latency_bounded=latency is not None and latency <= latency_bound,
        inject_localized=(
            delta is not None and bool(delta.any())
            and not bool((delta & ~true_delta).any())
        ),
        inject_no_cross_chip_fp=(
            eng.health.detections == 1
            and all(eng.health.state(c) == HEALTHY
                    for c in range(chips) if c != victim)
        ),
        inject_alert_fired=(
            eng.alerts.fired_total >= 1
            and "detect.new_faults" in eng.alerts.summary()["fired"]
            and bool(alert_names)
        ),
        healthy_fleet_zero_false_positives=(
            ctl_eng.health.detections == 0
            and all(ctl_eng.health.state(c) == HEALTHY for c in range(chips))
            and ctl_eng.alerts.fired_total == 0
        ),
        inject_other_chips_pinned=bool(others_pinned),
    )
    report = dict(
        chips=chips,
        victim=victim,
        probe_every=probe_every,
        injected_at_clock=injected.get("at"),
        detected_at_clock=detected_at,
        detection_latency_dispatches=latency,
        detection_latency_bound=latency_bound,
        true_new_faults=int(true_delta.sum()),
        reconstructed_faults=None if delta is None else int(delta.sum()),
        probe_dispatches=stats.probe_dispatches,
        wall_s=wall,
        health=eng.health.summary(),
        alerts=eng.alerts.summary(),
        checks=checks,
    )
    return report, checks, rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI scale")
    ap.add_argument("--fleet", action="store_true", help="add the sharded fleet tier")
    ap.add_argument("--heavy-traffic", action="store_true",
                    help="add the Poisson/Zipf bucketed-vs-unbucketed "
                         "admission benchmark (bounded page pool)")
    ap.add_argument("--inject-fault", action="store_true",
                    help="add the mid-serve fault-injection detection "
                         "benchmark: one fleet chip's silicon changes under "
                         "the engine; the ABFT probe/health/alert stack must "
                         "detect, localize and alert with zero false "
                         "positives elsewhere")
    ap.add_argument("--health-out", type=str, default=None, metavar="FILE",
                    help="write the per-chip health + alert summary JSON "
                         "(from --inject-fault and/or --heavy-traffic)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--out", type=str, nargs="?", const=CANONICAL_OUT,
                    default=None, metavar="FILE",
                    help=f"write the JSON report (no value: {CANONICAL_OUT})")
    ap.add_argument("--trace-out", type=str, default=None, metavar="FILE",
                    help="write the recorded spans (continuous/heavy serve + "
                         "fleet) as a Chrome trace for Perfetto")
    ap.add_argument(
        "--no-analysis", action="store_true",
        help="skip the static-analyzer section (donated-bytes fraction, "
        "recompile census) of the report",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import numpy as np

    from repro.configs import get_arch, reduce_config
    from repro.models import model as M

    cfg = reduce_config(get_arch("smollm-135m"))
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    trace, plen = build_trace(cfg, smoke=args.smoke)
    num_pages = 1 + sum(  # enough pages for everything at once; paging still wins
        -(-(plen + r.max_new_tokens) // args.page_size) for r in trace
    )

    static_out, static = run_static(
        cfg, params, trace, plen, num_slots=args.slots, page_size=args.page_size
    )
    cont_out, cont, cont_rec = run_continuous(
        cfg, params, trace,
        num_slots=args.slots, page_size=args.page_size, num_pages=num_pages,
    )
    trace_recorders = [cont_rec]  # heavy replaces this serve-proc recording

    tokens_match = set(static_out) == set(cont_out) and all(
        np.array_equal(static_out[r], cont_out[r]) for r in static_out
    )
    checks = dict(
        tokens_match=bool(tokens_match),
        fewer_dispatches=cont["decode_dispatches"] < static["decode_dispatches"],
        less_peak_kv=cont["peak_resident_kv_bytes"] < static["peak_resident_kv_bytes"],
        less_kv_byte_steps=cont["kv_byte_steps"] < static["kv_byte_steps"],
    )
    report = dict(
        arch=cfg.name,
        requests=len(trace),
        prompt_len=plen,
        budgets=[r.max_new_tokens for r in trace],
        num_slots=args.slots,
        page_size=args.page_size,
        static=static,
        continuous=cont,
        checks=checks,
    )
    if not args.no_analysis:
        # static-analyzer metrics ahead of the ROADMAP-1 prefill-bucketing
        # work: the donated-bytes fraction of every loop-carried serve/train
        # operand and the recompile census the measured `compiles` should
        # track (see src/repro/analysis/README.md)
        from repro.analysis import analyze_stack

        ana = analyze_stack("smollm-135m", passes=("donation", "recompile"))
        don = ana.passes["donation"]
        report["analysis"] = dict(
            donated_fraction=don["donated_fraction"],
            undonated_carried_bytes={
                name: e["undonated_carried_bytes"]
                for name, e in don["entries"].items()
            },
            trace_signatures={
                name: e["signatures"]
                for name, e in ana.passes["recompile"].items()
            },
            findings=len(ana.findings),
        )
        checks["all_carried_bytes_donated"] = don["donated_fraction"] == 1.0
    if args.fleet:
        report["fleet"], fleet_rec = run_fleet(
            cfg, params, trace, chips=args.chips,
            num_slots=args.slots, page_size=args.page_size, num_pages=num_pages,
        )
        checks["fleet_pinned"] = report["fleet"]["pinned_vs_per_chip_engines"]
        trace_recorders.append(fleet_rec)  # distinct proc: own Perfetto lane
    if args.heavy_traffic:
        heavy, heavy_checks, heavy_rec = run_heavy_traffic(
            cfg, params, smoke=args.smoke,
            num_slots=args.slots, page_size=args.page_size,
        )
        report["heavy_traffic"] = heavy
        checks.update(heavy_checks)
        # the heavy bucketed run is the richer serve-proc recording — it
        # replaces the base continuous one (both record proc="serve")
        trace_recorders[0] = heavy_rec
    if args.inject_fault:
        inject, inject_checks, inject_rec = run_inject_fault(
            cfg, params, smoke=args.smoke, chips=args.chips,
            num_slots=args.slots, page_size=args.page_size,
        )
        report["inject_fault"] = inject
        checks.update(inject_checks)
        trace_recorders.append(inject_rec)  # carries the alert swimlanes
    if args.health_out:
        health = {}
        if "inject_fault" in report:
            health["inject_fault"] = dict(
                health=report["inject_fault"]["health"],
                alerts=report["inject_fault"]["alerts"],
                detection_latency_dispatches=report["inject_fault"][
                    "detection_latency_dispatches"],
            )
        if "heavy_traffic" in report:
            health["heavy_traffic"] = report["heavy_traffic"]["detection"]
        with open(args.health_out, "w") as f:
            json.dump(health, f, indent=2)
        report["health_out"] = args.health_out
    if args.trace_out:
        from repro.obs import validate_chrome_trace, write_chrome_trace

        written = write_chrome_trace(args.trace_out, trace_recorders)
        checks["trace_out_valid"] = not validate_chrome_trace(written)
        report["trace_out"] = dict(
            path=args.trace_out, events=len(written["traceEvents"]),
            recorders=len(trace_recorders),
        )

    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if not all(checks.values()):
        failed = [k for k, v in checks.items() if not v]
        print(f"FAIL: {failed}", file=sys.stderr)
        return 1
    print(
        f"OK: continuous batching cut dispatches "
        f"{static['decode_dispatches']} -> {cont['decode_dispatches']} and peak "
        f"KV bytes {static['peak_resident_kv_bytes']} -> "
        f"{cont['peak_resident_kv_bytes']} with identical tokens",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
