"""The trace reduction on a small trace laid out as a v5e profile is: a
``/device:TPU:0`` plane with ``XLA Ops`` (nested) and ``XLA Modules``
lines, and the host's ``python`` line with the window annotation."""
import pytest
from jax.profiler import ProfileData

from bench import trace as T

OPS = [  # (name, start ns, duration ns) on the device
    ("%while.1 = (s32[]) while()", 1000, 400),  # decode program body
    ("%fusion.2 = bf16[8]{0} fusion()", 1100, 100),  # nested in the while
    ("%copy.3 = bf16[30,16]{1,0} copy()", 1250, 50),  # nested in the while
    ("%fusion.2 = bf16[8]{0} fusion()", 1600, 100),  # prefill program
    ("%masked_matmul_pallas.1 = bf16[5,1024]{1,0} custom-call()", 1800, 20),
]
MODULES = [
    ("jit_sample_decode(123)", 1000, 400),
    ("jit__prefill_chunk_fn(456)", 1600, 100),
    ("jit_masked_matmul_checksummed(789)", 1790, 40),
]
PYTHON = [
    ("bench.traced_window", 900, 1100),  # the window is [900, 2000)
    ("$continuous.py:744 serve", 950, 900),
    ("$continuous.py:244 record_step", 1420, 150),
]


def _events(rows, meta0):
    ev, meta = [], []
    for i, (name, start, dur) in enumerate(rows):
        mid = meta0 + i
        ev.append(f"events {{ metadata_id: {mid} offset_ps: {start * 1000} duration_ps: {dur * 1000} }}")
        meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{name}" }} }}')
    return " ".join(ev), " ".join(meta)


def _profile():
    ops, m1 = _events(OPS, 1)
    mods, m2 = _events(MODULES, 100)
    py, m3 = _events(PYTHON, 200)
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mods} }}
      {m1} {m2} }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 3 name: "python3" timestamp_ns: 0 {py} }}
      {m3} }}
    """
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def tr():
    return T.reduce(_profile())


def test_window_and_busy(tr):
    assert (tr.t0, tr.t1) == (900, 2000)
    assert tr.window_s == pytest.approx(1100e-9)
    # union of [1000,1400) [1600,1700) [1800,1820): nested ops count once
    assert tr.busy_s() == pytest.approx(520e-9)


def test_modules(tr):
    assert tr.module_spans("jit_sample_decode") == [(1000, 1400)]
    assert tr.module_time_s("jit__prefill_chunk_fn") == pytest.approx(100e-9)
    assert tr.module_spans("jit_masked_matmul_checksummed") == [(1790, 1830)]


def test_top_ops_by_self_time(tr):
    top = dict(tr.top_ops(10))
    assert top["while.1 (s32[])"] == pytest.approx(250e-9)  # 400 less 100 and 50 nested
    assert top["fusion.2 bf16[8]"] == pytest.approx(200e-9)  # summed over both runs
    assert top["copy.3 bf16[30,16]"] == pytest.approx(50e-9)


def test_idle_gaps_named_by_host_call(tr):
    gaps = tr.idle_gaps(10)
    lengths = [g[1] for g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert sum(lengths) == pytest.approx(tr.window_s - tr.busy_s())
    by_len = {round(g[1] * 1e9): g[0] for g in gaps}
    assert by_len[200].startswith("host: $continuous.py:244 record_step")  # [1400, 1600)
    assert by_len[100] == "host: $continuous.py:744 serve"  # [900, 1000)
    assert by_len[180] == "host: after $continuous.py:744 serve"  # [1820, 2000)


def test_op_and_module_names():
    assert T.module_name("jit_sample_decode(14166653710771829670)") == "jit_sample_decode"
    assert T.op_name("%fusion.8 = bf16[28311552]{0:T(1024)} fusion(bf16[256,256] %x)") == \
        "fusion.8 bf16[28311552]"


def test_a_trace_without_the_window_annotation_is_refused():
    ops, meta = _events(OPS, 1)
    text = f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 name: "XLA Ops" {ops} }} {meta} }}'
    with pytest.raises(RuntimeError, match="annotation"):
        T.reduce(ProfileData.from_text_proto(text))
