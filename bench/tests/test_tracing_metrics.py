"""The readers of the engine's round stages, queue spans and published
fault-mask ops, on synthetic recorder events and a synthetic trace; and a
traced rehearsal of a cell, in which they run on what the program records."""
import json
from types import SimpleNamespace

import pytest

from bench import run
from bench.trace import Device, Trace


def span(name, t0, t1, **args):
    return SimpleNamespace(kind="span", name=name, ts=t0, dur=t1 - t0, args=args)


def instant(name, ts, **args):
    return SimpleNamespace(kind="instant", name=name, ts=ts, dur=None, args=args)


def view(events, trace=None, t_w0=0.0, t_w1=10.0, **mix):
    job = SimpleNamespace(events=events, t_w0=t_w0, t_w1=t_w1, window_s=t_w1 - t_w0, mix=mix)
    return SimpleNamespace(job=job, trace=trace)


read_mask = run.load_reader("fault_mask_share.decode")
read_host = run.load_reader("host_ms_per_round.serve")
read_queue = run.load_reader("queue_wait_p95_ms.serve")

PROGRAMS = instant("serve.programs", 0.0,
                   fault_mask={"jit_sample_decode": ["fusion.8", "fusion.9"],
                               "jit__prefill_chunk_fn": ["fusion.8"]})
TRACE = Trace(t0=0, t1=3000, python=[], devices={"/device:TPU:0": Device(
    modules=[("jit_sample_decode", 100, 1100),
             ("jit__prefill_chunk_fn", 1200, 1400),
             ("jit_sample_decode", 1500, 2500),
             ("jit_sample_decode", 2800, 3800)],  # runs past the window's end
    ops=[("%while.1 = (s32[]) while()", 150, 1050),
         ("%fusion.8 = bf16[28311552]{0} fusion()", 200, 600),
         ("%fusion.9 = bf16[8]{0} fusion()", 300, 400),  # nested in fusion.8
         ("%dot.3 = bf16[8]{0} dot()", 600, 1000),
         ("%fusion.8 = bf16[8]{0} fusion()", 1250, 1350),  # the prefill's own fusion.8
         ("%fusion.9 = bf16[8]{0} fusion()", 1600, 1700),
         ("%fusion.8 = bf16[8]{0} fusion()", 2900, 3000)],
)})


def test_mask_share_counts_nested_mask_ops_once_in_whole_decode_runs():
    # runs [100,1100) and [1500,2500): mask time 400 + 100 of 2000
    assert read_mask(view([PROGRAMS], TRACE)) == pytest.approx(25.0)


def test_mask_share_is_zero_without_mask_ops_and_absent_without_the_map():
    healthy = instant("serve.programs", 0.0, fault_mask={"jit_sample_decode": []})
    assert read_mask(view([healthy], TRACE)) == 0.0
    assert read_mask(view([], TRACE)) is None  # a program that publishes no map
    assert read_mask(view([PROGRAMS], None)) is None


ROUNDS = [
    span("serve_round", 0.5, 1.5, clock=0),  # inside the profiled stretch [1, 3]
    span("prefill.wait", 0.6, 0.7),
    span("serve_round", 4.0, 4.010, clock=1),
    span("prefill.wait", 4.001, 4.003),
    span("decode.wait", 4.005, 4.008),  # host 0.010 - 0.002 - 0.003 = 5 ms
    span("serve_round", 5.0, 5.004, clock=2),
    span("decode.wait", 5.001, 5.002),  # host 3 ms
    span("serve_round", 6.0, 6.009, clock=3),
    span("decode.wait", 6.001, 6.003),  # host 7 ms
]


def test_host_per_round_is_the_median_less_device_waits_outside_the_trace():
    assert read_host(view(ROUNDS, trace_start=1.0, trace_seconds=2.0)) == pytest.approx(5.0)
    # the whole window profiled: no round left to read
    assert read_host(view(ROUNDS, trace_start=0.0, trace_seconds=10.0)) is None
    assert read_host(view([])) is None


def test_queue_wait_p95_censors_requests_still_queued_at_the_cut():
    events = [instant("enqueue", float(i), rid=i) for i in range(10)]
    events += [span("queue", float(i), i + 0.1, rid=i) for i in range(9)]
    events += [instant("enqueue", 11.0, rid=10)]  # after the cut
    # nine waits of 100 ms and one of 1 s, still queued at t_w1 = 10
    got = read_queue(view(events))
    assert got == pytest.approx(100.0 + 0.55 * 900.0)
    assert read_queue(view(events[:10])) is None  # a program that records no queue spans


def test_a_traced_rehearsal_reports_the_span_metrics(capsys):
    argv = ["--workload", "smollm-135m.chat.faulty", "--seed", str(2**35 + 11),
            "--seconds", "1", "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the CPU trace holds no device plane, and the whole window is profiled
    assert "queue_wait_p95_ms.serve" in line["metrics"]
    assert "fault_mask_share.decode" not in line["metrics"]
    assert "host_ms_per_round.serve" not in line["metrics"]
