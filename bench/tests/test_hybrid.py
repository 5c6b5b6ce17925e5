"""The hybrid cell's readers on a synthetic trace whose op events are named
as on a TPU, with no scope path (``%fusion.8 = bf16[...] fusion(...)``):
each returns a number from the engine's published ``serve.programs`` map,
and raises where the map is absent. Also: the scan's counts, and a traced
rehearsal of the cell on the CPU, whose trace holds no device plane."""
import json
from types import SimpleNamespace

import pytest

from bench import run
from bench.peaks import PEAKS
from bench.reference.jamba import dims
from bench.ssm_flops import scan_flops_bytes
from bench.trace import Device, Trace

CELL = "jamba2-3b.longdoc.healthy"
read_roof = run.load_reader("ssm_scan_roofline")
read_prefill = run.load_reader("mamba_share.prefill")
read_decode = run.load_reader("mamba_share.decode")
MODEL = json.loads((run.ROOT / "bench" / "configs" / "jamba2-3b.json").read_text())

PROGRAMS = SimpleNamespace(kind="instant", name="serve.programs", ts=0.0, dur=None, args={
    "fault_mask": {"jit_sample_decode": [], "jit__prefill_chunk_fn": []},
    "mamba": {"jit_sample_decode": ["fusion.3", "fusion.4"],
              "jit__prefill_chunk_fn": ["fusion.3", "custom-call.1", "fusion.7"]},
    "ssm_scan": {"jit_sample_decode": ["fusion.4"],
                 "jit__prefill_chunk_fn": ["custom-call.1"]},
})
TRACE = Trace(t0=0, t1=10_000_000, python=[], devices={"/device:TPU:0": Device(
    modules=[("jit__prefill_chunk_fn", 100, 2_000_100),
             ("jit_sample_decode", 3_000_000, 4_000_000),
             ("jit__prefill_chunk_fn", 5_000_000, 7_000_000),
             ("jit_sample_decode", 9_500_000, 10_500_000)],  # past the window's end
    ops=[("%while.2 = (s32[]) while()", 200, 1_900_000),
         ("%fusion.3 = bf16[256,10240]{1,0} fusion()", 300, 400_300),
         ("%custom-call.1 = (bf16[1,256,5120]{2,1,0}) custom-call()", 500_000, 1_500_000),
         ("%fusion.7 = bf16[1,256,5120]{2,1,0} fusion()", 1_400_000, 1_600_000),  # overlaps
         ("%fusion.9 = bf16[256,8192]{1,0} fusion()", 1_600_000, 1_900_000),  # the MLP
         ("%fusion.3 = bf16[16,10240]{1,0} fusion()", 3_100_000, 3_300_000),
         ("%fusion.4 = f32[16,5120,16]{2,1,0} fusion()", 3_300_000, 3_400_000),
         ("%fusion.9 = bf16[16,8192]{1,0} fusion()", 3_400_000, 3_900_000),
         ("%custom-call.1 = (bf16[1,256,5120]{2,1,0}) custom-call()", 5_100_000, 6_100_000),
         ("%fusion.4 = f32[16,5120,16]{2,1,0} fusion()", 9_600_000, 9_700_000)],
)})


def view(events, trace=TRACE):
    job = SimpleNamespace(events=events, model={k: MODEL[k] for k in MODEL if k != "serve"},
                          sv=MODEL["serve"])
    return SimpleNamespace(job=job, trace=trace, peaks=PEAKS["TPU v5 lite"])


def test_mamba_shares_read_the_published_ops_of_whole_runs():
    # prefill runs 2.0 + 2.0 ms; mamba ops cover [300, 400300) + [500000,
    # 1600000) of the first and [5100000, 6100000) of the second
    assert read_prefill(view([PROGRAMS])) == pytest.approx(100 * 2_500_000 / 4_000_000)
    # the one whole decode run: [3100000, 3400000) of 1 ms
    assert read_decode(view([PROGRAMS])) == pytest.approx(30.0)


def test_scan_roofline_is_the_least_time_over_the_scan_ops_union():
    m = dims(view([]).job.model)
    flops, byts = scan_flops_bytes(MODEL["serve"]["chunk_size"], m["di"], m["n"])
    peaks = PEAKS["TPU v5 lite"]
    least = m["Ls"] * max(flops / peaks.flops, byts / peaks.hbm_bw)
    got = read_roof(view([PROGRAMS]))
    assert got == pytest.approx(100 * 2 * least / 2e-3)
    assert 0 < got <= 100


@pytest.mark.parametrize("read", [read_roof, read_prefill, read_decode])
def test_a_reader_raises_without_the_map_and_reads_nothing_without_a_trace(read):
    with pytest.raises(RuntimeError, match="serve.programs"):
        read(view([]))
    old = SimpleNamespace(**{**vars(PROGRAMS), "args": {"fault_mask": PROGRAMS.args["fault_mask"]}})
    with pytest.raises(RuntimeError, match="map"):  # a program that maps no SSM scope
        read(view([old]))
    assert read(view([PROGRAMS], trace=None)) is None
    cpu = Trace(t0=0, t1=1, python=[], devices={})
    assert read(view([PROGRAMS], trace=cpu)) is None


def test_a_window_without_a_whole_run_raises():
    early = Trace(t0=0, t1=50, python=[], devices=TRACE.devices)
    with pytest.raises(RuntimeError, match="no whole run"):
        read_prefill(view([PROGRAMS], trace=early))


def test_scan_counts_at_the_chunk_shape():
    flops, byts = scan_flops_bytes(256, 5120, 16)
    assert flops == 7.0 * 256 * 5120 * 16 + 3.0 * 256 * 5120
    # u and y bf16, dt float32, B and C bf16; A, D, h0 and h_last float32
    assert byts == 256 * 5120 * 8 + 2 * 256 * 16 * 2 + 4 * (5120 * 16 + 5120) + 8 * 5120 * 16


def test_a_traced_rehearsal_of_the_cell_runs(capsys):
    argv = ["--workload", CELL, "--seed", str(2**35 + 11), "--seconds", "1", "--trace", "1",
            "--rehearse"]
    assert run.main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    # the CPU trace holds no device plane: the device readers read nothing
    assert not {"ssm_scan_roofline", "mamba_share.prefill", "mamba_share.decode"} & set(
        line["metrics"])
