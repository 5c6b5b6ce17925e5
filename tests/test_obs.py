"""Observability layer tests (repro.obs + its hook sites).

Contracts pinned here:
* the ring buffer is bounded: overwrite-oldest, oldest-first iteration,
  dropped accounting;
* histogram bucket edges use ``le`` semantics (a value equal to an edge
  lands in that bucket), NaN observations are skipped, and percentiles are
  exact until the raw-sample store truncates (then bucket-interpolated);
* NULL_RECORDER is falsy, un-enableable, and every record call on a
  disabled recorder is a no-op;
* Chrome/JSONL exporters round-trip losslessly and the schema validator
  actually rejects malformed traces;
* the PageAllocator guards double frees and foreign pages instead of
  corrupting the free list, and counts high-water/alloc-failures;
* instrumenting ContinuousBatchingEngine changes ZERO sampled tokens
  (bitwise, greedy) and emits a complete, well-nested request lifecycle
  even under mid-flight admissions into freed slots;
* the ``repro.launch.obs`` CLI self-check passes.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduce_config
from repro.models import model as M
from repro.obs import (
    NULL_RECORDER,
    Recorder,
    RingBuffer,
    chrome_trace,
    jsonl_to_chrome,
    read_jsonl,
    validate_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.metrics import TTFT_BUCKETS_S, Histogram, MetricsRegistry
from repro.serve import ContinuousBatchingEngine, PageAllocator, Request

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------


def test_ring_buffer_wraparound():
    rb = RingBuffer(4)
    for i in range(3):
        rb.append(i)
    assert list(rb) == [0, 1, 2] and rb.dropped == 0
    for i in range(3, 10):
        rb.append(i)
    assert len(rb) == 4
    assert list(rb) == [6, 7, 8, 9]  # oldest-first after wrap
    assert rb.dropped == 6
    with pytest.raises(ValueError):
        RingBuffer(0)


def test_recorder_ring_is_bounded_and_drop_counted():
    rec = Recorder(capacity=8)
    for i in range(20):
        rec.instant(f"e{i}")
    assert len(rec.event_list()) == 8
    assert rec.events.dropped == 12
    assert [e.name for e in rec.event_list()] == [f"e{i}" for i in range(12, 20)]
    assert rec.summary()["events_dropped"] == 12


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_bucket_edges_le_semantics():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
        h.observe(v)
    # v <= edge lands in that bucket: 1.0 joins [.., 1.0], 2.0 joins (1, 2],
    # 4.0 joins (2, 4], 9.0 overflows to +inf
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6 and h.min == 0.5 and h.max == 9.0
    h.observe(float("nan"))  # skipped, not counted anywhere
    assert h.count == 6
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("empty", buckets=())


def test_histogram_percentiles_exact_then_interpolated():
    h = Histogram("h", buckets=(10.0, 20.0, 40.0), max_samples=1000)
    vals = list(range(1, 101))
    for v in vals:
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(np.percentile(vals, 50))
    assert h.percentile(99) == pytest.approx(np.percentile(vals, 99))
    # truncate the raw store: percentile falls back to bucket interpolation,
    # staying inside the right bucket
    t = Histogram("t", buckets=(10.0, 20.0, 40.0), max_samples=10)
    for v in vals:
        t.observe(float(v))
    assert t.samples_truncated
    # interpolation stays close to truth: true p50 = 50.5, p99 = 99.01
    assert t.percentile(50) == pytest.approx(50.5, abs=2.0)
    assert t.percentile(99) == pytest.approx(99.0, abs=2.0)
    d = t.as_dict()
    assert d["samples_truncated"] and d["count"] == 100


def test_metrics_registry_type_conflicts_and_counter_monotonicity():
    reg = MetricsRegistry()
    reg.counter("n").inc(3)
    assert reg.counter("n").value == 3
    with pytest.raises(TypeError):
        reg.gauge("n")
    with pytest.raises(ValueError):
        reg.counter("n").inc(-1)
    with pytest.raises(ValueError):
        reg.histogram("h")  # new histogram needs buckets
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    assert reg.histogram("h").count == 1  # registered: buckets optional
    g = reg.gauge("g")
    g.set(5)
    g.set(2)
    assert g.value == 2 and g.high_water == 5
    g2 = reg.gauge("g2")
    g2.set(-3)  # first set pins high-water even when negative
    assert g2.high_water == -3


# ---------------------------------------------------------------------------
# recorder + null recorder
# ---------------------------------------------------------------------------


def test_null_recorder_is_falsy_noop_and_unenableable():
    assert not NULL_RECORDER
    NULL_RECORDER.instant("x")
    NULL_RECORDER.span("x", t0=0.0, t1=1.0)
    NULL_RECORDER.sample("x", 1.0)
    NULL_RECORDER.count("x")
    NULL_RECORDER.observe("x", 1.0, buckets=(1.0,))
    assert len(NULL_RECORDER.event_list()) == 0
    assert NULL_RECORDER.metrics.names() == []
    with pytest.raises(AttributeError):
        NULL_RECORDER.enabled = True


def test_disabled_recorder_records_nothing():
    rec = Recorder(enabled=False)
    assert not rec
    rec.instant("x")
    rec.count("x")
    with rec.timed("block"):
        pass
    assert len(rec.event_list()) == 0 and rec.metrics.names() == []


def test_recorder_timed_and_sample_mirror_gauge():
    rec = Recorder()
    with rec.timed("work", track="t"):
        pass
    (ev,) = rec.event_list()
    assert ev.kind == "span" and ev.name == "work" and ev.dur >= 0.0
    rec.sample("pool.free", 7, track="pages")
    rec.sample("pool.free", 3, track="pages")
    g = rec.metrics.gauge("pool.free")
    assert g.value == 3 and g.high_water == 7


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _recorded():
    rec = Recorder()
    t0 = rec.now()
    rec.span("admit", proc="serve", track="slot0", t0=t0, t1=t0 + 0.01,
             args=dict(rid=0))
    rec.span("decode", proc="serve", track="slot0", t0=t0 + 0.01, t1=t0 + 0.03,
             args=dict(rid=0, tokens=3))
    rec.instant("retire", proc="serve", track="slot0", args=dict(rid=0))
    rec.sample("kv.free_pages", 5, proc="serve", track="pages")
    rec.span("fit_chunk", proc="train", track="engine", t0=t0, t1=t0 + 0.02)
    rec.count("serve.tokens_emitted", 3)
    rec.observe("serve.ttft_wall_s", 0.01, TTFT_BUCKETS_S)
    return rec


def test_chrome_trace_schema_and_lane_mapping():
    rec = _recorded()
    tr = chrome_trace(rec)
    assert validate_chrome_trace(tr) == []
    evs = tr["traceEvents"]
    procs = {e["args"]["name"]: e["pid"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert set(procs) == {"serve", "train"}  # one pid lane per proc
    tids = {(e["pid"], e["args"]["name"]) for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert (procs["serve"], "slot0") in tids and (procs["serve"], "pages") in tids
    spans = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    counters = [e for e in evs if e["ph"] == "C"]
    assert counters and all("value" in e["args"] for e in counters)


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace({"nope": 1})
    assert validate_chrome_trace(dict(traceEvents=[]))
    # span missing dur, counter missing value, unnamed pid
    bad = dict(traceEvents=[
        dict(ph="X", name="s", pid=1, tid=1, ts=0.0),
        dict(ph="C", name="c", pid=1, tid=1, ts=0.0, args={}),
    ])
    problems = validate_chrome_trace(bad)
    assert any("dur" in p for p in problems)
    assert any("value" in p for p in problems)
    assert any("process_name" in p for p in problems)
    assert validate_chrome_trace("/nonexistent/trace.json")


def test_jsonl_round_trip_and_convert(tmp_path):
    rec = _recorded()
    log = tmp_path / "run.jsonl"
    write_jsonl(str(log), rec)
    back = read_jsonl(str(log))
    assert back["meta"]["version"] == 1
    assert back["events"] == rec.event_list()  # lossless, order-preserving
    names = {m["name"]: m for m in back["metrics"]}
    assert names["serve.tokens_emitted"]["value"] == 3
    assert names["serve.ttft_wall_s"]["count"] == 1
    out = tmp_path / "run.trace.json"
    tr = jsonl_to_chrome(str(log), str(out))
    assert validate_chrome_trace(tr) == []
    assert validate_chrome_trace(str(out)) == []
    with open(out) as f:
        assert json.load(f)["traceEvents"]
    with pytest.raises(ValueError, match="meta"):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        read_jsonl(str(empty))


def test_write_chrome_trace_merges_recorders(tmp_path):
    serve_rec = _recorded()
    train_rec = Recorder()
    train_rec.instant("schedule", proc="train", track="scheduler")
    out = tmp_path / "merged.json"
    tr = write_chrome_trace(str(out), [serve_rec, train_rec])
    assert validate_chrome_trace(tr) == []
    pids = {e["args"]["name"] for e in tr["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert pids == {"serve", "train"}


# ---------------------------------------------------------------------------
# page allocator guards + counters
# ---------------------------------------------------------------------------


def test_page_allocator_double_free_and_foreign_page_guards():
    a = PageAllocator(num_pages=8, page_size=4)
    chain = a.alloc(3)
    a.free(chain)
    with pytest.raises(ValueError, match="double free"):
        a.free(chain[:1])
    b = PageAllocator(num_pages=32, page_size=4)
    other = b.alloc(20)
    with pytest.raises(ValueError, match="foreign"):
        a.free(other[-1:])  # page id from a bigger pool: a never had it
    with pytest.raises(ValueError, match="foreign"):
        a.free([0])  # the reserved scratch page
    # the guards kept the free list intact: the full pool still allocates
    assert len(a.alloc(7)) == 7


def test_page_allocator_high_water_and_alloc_failures():
    a = PageAllocator(num_pages=6, page_size=4)  # 5 usable (page 0 reserved)
    assert a.high_water == 0 and a.alloc_failures == 0
    c1 = a.alloc(3)
    assert a.high_water == 3
    a.free(c1)
    assert a.high_water == 3  # monotone across frees
    assert not a.can_alloc(6)
    assert a.alloc_failures == 1  # backpressure stall counted
    with pytest.raises(MemoryError):
        a.alloc(6)
    assert a.alloc_failures == 2
    a.alloc(5)
    assert a.high_water == 5


# ---------------------------------------------------------------------------
# engine instrumentation: zero token impact + complete lifecycle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_model():
    cfg = reduce_config(get_arch("smollm-135m"))
    params, _ = M.init_params(cfg, KEY)
    return cfg, params


def _trace_reqs(cfg):
    def prompt(seed, n):
        return np.asarray(jax.random.randint(
            jax.random.fold_in(KEY, seed), (n,), 0, cfg.vocab_size
        ))

    # 5 requests through 2 slots: rids 2-4 are admitted mid-flight into
    # freed slots; rid 3 exceeds the top bucket so it takes the chunked path
    return [
        Request(0, prompt(0, 6), max_new_tokens=4),
        Request(1, prompt(1, 7), max_new_tokens=10),
        Request(2, prompt(2, 8), max_new_tokens=5, arrival=2),
        Request(3, prompt(3, 20), max_new_tokens=3, arrival=4),
        Request(4, prompt(4, 6), max_new_tokens=6, arrival=4),
    ]


def _engine(cfg, params, recorder):
    return ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=4, num_pages=32,
        prefill_buckets=(8, 16), chunk_size=8, recorder=recorder,
    )


def test_recorder_changes_zero_sampled_tokens(served_model):
    """THE observability pin: greedy token streams are bitwise identical
    with the recorder off and on — hooks are host-side only."""
    cfg, params = served_model
    reqs = _trace_reqs(cfg)
    off, _ = _engine(cfg, params, None).serve(reqs)
    rec = Recorder()
    on, _ = _engine(cfg, params, rec).serve(reqs)
    assert set(off) == set(on)
    for rid in off:
        assert np.array_equal(off[rid].tokens, on[rid].tokens), rid
        np.testing.assert_array_equal(off[rid].logprobs, on[rid].logprobs)
    assert len(rec.event_list()) > 0  # the instrumented run did record


def test_request_lifecycle_spans_nest_under_midflight_admissions(served_model):
    cfg, params = served_model
    reqs = _trace_reqs(cfg)
    rec = Recorder()
    outs, _ = _engine(cfg, params, rec).serve(reqs)
    evs = rec.event_list()
    rids = set(outs)

    def of(name):
        return [e for e in evs if e.name == name]

    admit = {e.args["rid"]: e for e in of("admit")}
    chunks = {}
    for e in of("chunk"):
        chunks.setdefault(e.args["rid"], []).append(e)
    decode = {e.args["rid"]: e for e in of("decode")}
    retire = {e.args["rid"]: e for e in of("retire")}
    enq = {e.args["rid"] for e in of("enqueue")}

    # complete lifecycle per retired rid; rid 3 chunked, the rest bucketed
    assert set(decode) == set(retire) == enq == rids
    assert set(admit) == rids - {3} and set(chunks) == {3}
    assert len(chunks[3]) == 3  # 20 tokens / chunk_size 8
    assert [c.args["final"] for c in sorted(chunks[3], key=lambda e: e.ts)] \
        == [False, False, True]

    for rid in rids:
        first = admit[rid] if rid in admit else sorted(
            chunks[rid], key=lambda e: e.ts)[-1]
        d = decode[rid]
        # nesting: admission closes before (or exactly when) decode begins,
        # decode closes before the retire instant
        assert first.ts + first.dur <= d.ts + 1e-9, rid
        assert d.ts + d.dur <= retire[rid].ts + 1e-9, rid
        assert d.args["tokens"] == len(outs[rid].tokens)

    # per-slot tracks never overlap: a slot serves one request at a time
    for track in {e.track for e in evs if e.track.startswith("slot")}:
        spans = sorted(
            (e for e in evs if e.track == track and e.kind == "span"
             and e.name in ("admit", "chunk", "decode")),
            key=lambda e: e.ts,
        )
        for a, b in zip(spans, spans[1:]):
            assert a.ts + a.dur <= b.ts + 1e-9, (track, a.name, b.name)

    # dispatch-level spans + pool samples + compile gauges landed too
    assert of("decode_step") and of("serve.end")
    assert any(e.kind == "sample" and e.name == "kv.free_pages" for e in evs)
    assert "serve.compiles.total" in rec.metrics
    # and the whole recording exports to a valid Chrome trace
    assert validate_chrome_trace(chrome_trace(rec)) == []


def test_recorder_histograms_cover_all_requests(served_model):
    cfg, params = served_model
    reqs = _trace_reqs(cfg)
    rec = Recorder()
    outs, stats = _engine(cfg, params, rec).serve(reqs)
    m = rec.summary()["metrics"]
    assert m["serve.ttft_wall_s"]["count"] == len(reqs)
    assert m["serve.queue_wait_steps"]["count"] == len(reqs)
    assert m["serve.requests_retired"]["value"] == len(reqs)
    assert m["serve.tokens_emitted"]["value"] == stats.emitted_tokens
    steps = [e for e in rec.event_list() if e.kind == "span" and e.name == "decode_step"]
    assert len(steps) == stats.decode_dispatches
    assert not math.isnan(m["serve.ttft_wall_s"]["p99"])


# ---------------------------------------------------------------------------
# round stages, queue spans, the mask's scope and the profiler's timeline
# ---------------------------------------------------------------------------

STAGES = ("serve_round", "schedule", "prefill.wait", "decode.dispatch",
          "decode.wait", "decode.fetch", "record_step", "health")


def _faulty_ctx(cfg, rate=0.1):
    from repro.core import from_fault_map
    from repro.core.faults import FaultMap

    rng = np.random.default_rng(3)
    return from_fault_map(FaultMap(rng.random((cfg.array_rows, cfg.array_cols)) < rate))


def _staged_engine(cfg, params, recorder, ctx=None):
    return ContinuousBatchingEngine(
        cfg, params, ctx, num_slots=2, page_size=4, num_pages=32,
        prefill_buckets=(8, 16), chunk_size=8, recorder=recorder, probe_every=2,
    )


def _host_spans(rec):
    return sorted((e for e in rec.event_list() if e.kind == "span" and e.track == "host"),
                  key=lambda e: (e.ts, -e.dur))


def test_every_round_is_one_span_holding_its_stages(served_model):
    cfg, params = served_model
    rec = Recorder()
    _, stats = _staged_engine(cfg, params, rec).serve(_trace_reqs(cfg))
    host = _host_spans(rec)
    assert {e.name for e in host} == set(STAGES)
    rounds = [e for e in host if e.name == "serve_round"]
    for a, b in zip(rounds, rounds[1:]):
        assert a.ts + a.dur <= b.ts and a.args["clock"] < b.args["clock"]
    per_round = [[] for _ in rounds]
    starts = [r.ts for r in rounds]
    for e in host:
        if e.name == "serve_round":
            continue
        i = int(np.searchsorted(starts, e.ts, side="right")) - 1
        assert i >= 0 and e.ts + e.dur <= rounds[i].ts + rounds[i].dur + 1e-12, e.name
        per_round[i].append(e.name)
    assert all(names.count("schedule") == 1 for names in per_round)
    decoding = [names for names in per_round if "decode.dispatch" in names]
    assert len(decoding) == stats.decode_dispatches
    for names in decoding:
        assert [n for n in names if n != "prefill.wait"] == [
            "schedule", "decode.dispatch", "decode.wait", "decode.fetch", "record_step",
            "health"]
    assert sum(names.count("prefill.wait") for names in per_round) == stats.prefill_dispatches
    # the harness's decode_step span holds its dispatch, wait and copies
    steps = sorted((e for e in rec.event_list() if e.name == "decode_step"), key=lambda e: e.ts)
    inner = [e for e in host if e.name in ("decode.dispatch", "decode.fetch")]
    for step, (d, f) in zip(steps, zip(inner[::2], inner[1::2])):
        assert step.ts <= d.ts and f.ts + f.dur <= step.ts + step.dur


def test_each_request_waits_in_one_queue_span(served_model):
    cfg, params = served_model
    rec = Recorder()
    outs, _ = _staged_engine(cfg, params, rec).serve(_trace_reqs(cfg))
    evs = rec.event_list()
    enq = {e.args["rid"]: e for e in evs if e.name == "enqueue"}
    queue = [e for e in evs if e.name == "queue"]
    assert sorted(e.args["rid"] for e in queue) == sorted(outs)
    first = {}
    for e in sorted((e for e in evs if e.name in ("admit", "chunk")), key=lambda e: e.ts):
        first.setdefault(e.args["rid"], e)
    for q in queue:
        rid = q.args["rid"]
        assert q.ts == enq[rid].ts
        assert q.ts + q.dur == pytest.approx(first[rid].ts, abs=1e-12)
        assert q.track == first[rid].track
    assert first[3].name == "chunk"  # the chunked request's wait ends at its first chunk


def _python_line(directory):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(directory / "**" / "*.xplane.pb"), recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/host:") and line.name.startswith("python"):
                return [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    return []


def test_stage_annotations_land_on_the_profilers_host_line(served_model, tmp_path):
    cfg, params = served_model
    rec = Recorder()
    eng = _staged_engine(cfg, params, rec)
    with jax.profiler.trace(str(tmp_path / "on")):
        eng.serve(_trace_reqs(cfg))
    marks = sorted((r for r in _python_line(tmp_path / "on") if r[0] in STAGES),
                   key=lambda r: (r[1], -r[2]))
    assert [r[0] for r in marks] == [e.name for e in _host_spans(rec)]
    with jax.profiler.trace(str(tmp_path / "off")):
        _staged_engine(cfg, params, None).serve(_trace_reqs(cfg))
    assert not [r for r in _python_line(tmp_path / "off") if r[0] in STAGES]


def test_fault_mask_scope_names_the_decode_programs_mask_ops(served_model):
    """A faulty chip's weights are masked once, by the premask program: no
    serving program holds an op under the fault-mask scope, and the
    published map still names every program, each with an empty list, as on
    a healthy chip."""
    from repro.core.masking import is_array_mapped

    cfg, params = served_model
    faulty = _staged_engine(cfg, params, Recorder(), _faulty_ctx(cfg))
    faulty.warmup()
    assert all("/fault_mask/" not in exe.as_text() for exe in faulty._aot.values())
    assert "jit_sample_decode" in faulty.mask_ops
    assert not any(faulty.mask_ops.values())
    faulty.serve(_trace_reqs(cfg)[:1])
    (published,) = [e for e in faulty.obs.event_list() if e.name == "serve.programs"]
    assert published.args["fault_mask"] == faulty.mask_ops
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    gemm = [w for path, w in flat if is_array_mapped(path, w)]
    premask = faulty._premask.lower(gemm, params["embed"], faulty.ctx.ok).compile()
    assert "/fault_mask/" in premask.as_text()
    healthy = _staged_engine(cfg, params, None)
    healthy.warmup()
    assert "/fault_mask/" not in healthy._aot[("decode",)].as_text()
    assert set(healthy.mask_ops) == set(faulty.mask_ops)
    assert not any(healthy.mask_ops.values())


def test_a_premask_is_counted_at_build_and_on_set_silicon(served_model):
    """Each premask counts once and is a span on the host track: one at
    build, one per set_silicon, the second through the program the first
    compiled; a healthy engine premasks nothing."""
    cfg, params = served_model
    rec = Recorder()
    eng = _staged_engine(cfg, params, rec, _faulty_ctx(cfg))
    assert rec.metrics.counter("fault_mask.premask").value == 1
    eng.warmup()
    eng.set_silicon(_faulty_ctx(cfg, rate=0.2))
    assert rec.metrics.counter("fault_mask.premask").value == 2
    spans = [e for e in rec.event_list() if e.name == "premask"]
    assert len(spans) == 2 and all(e.kind == "span" and e.track == "host" for e in spans)
    assert eng._premask._cache_size() == 1
    eng.serve(_trace_reqs(cfg)[:2])
    assert eng.compile_counts()["jit_fallback"] == 0
    quiet = Recorder()
    _staged_engine(cfg, params, quiet)
    assert quiet.metrics.counter("fault_mask.premask").value == 0
    assert not [e for e in quiet.event_list() if e.name == "premask"]


def test_a_forced_aot_miss_records_one_compile_fallback(served_model):
    cfg, params = served_model
    rec = Recorder()
    eng = _staged_engine(cfg, params, rec)
    eng.warmup()
    del eng._aot[("decode",)]
    eng.serve(_trace_reqs(cfg))
    (fb,) = [e for e in rec.event_list() if e.name == "compile.fallback"]
    assert fb.args == dict(program="('decode',)", clock=0)
    assert eng.compile_counts()["jit_fallback"] == 1


def test_recorder_changes_zero_sampled_tokens_on_a_faulty_chip(served_model):
    cfg, params = served_model
    reqs = _trace_reqs(cfg)
    ctx = _faulty_ctx(cfg)
    off, _ = _staged_engine(cfg, params, None, ctx).serve(reqs)
    on, _ = _staged_engine(cfg, params, Recorder(), ctx).serve(reqs)
    assert set(off) == set(on)
    for rid in off:
        assert np.array_equal(off[rid].tokens, on[rid].tokens), rid
        np.testing.assert_array_equal(off[rid].logprobs, on[rid].logprobs)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_obs_cli_check_convert_summary(tmp_path, capsys):
    from repro.launch.obs import main as obs_main

    assert obs_main(["--check"]) == 0
    log = tmp_path / "run.jsonl"
    write_jsonl(str(log), _recorded())
    out = tmp_path / "run.trace.json"
    assert obs_main(["--convert", str(log), "--trace-out", str(out)]) == 0
    assert validate_chrome_trace(str(out)) == []
    capsys.readouterr()  # drain the check/convert chatter
    assert obs_main(["--summary", str(log)]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["events"] == 5
    assert summary["metrics"]["serve.tokens_emitted"]["value"] == 3
