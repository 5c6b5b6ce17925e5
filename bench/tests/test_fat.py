"""The FAT job at a tiny size on the CPU: the plan's law, the reference's
batches against the program's, and what the check reads of the program's
``train_batch``, of the engine path beneath it, and of the control."""
import json

import jax
import numpy as np
import pytest

from bench import run, traffic
from bench.jobs import fat
from bench.reference import model as ref
from bench.reference import train as rtrain

MIX = traffic.load_mix("fleet8")
CONF = json.loads((run.ROOT / "bench" / "configs" / "smollm-135m.json").read_text())


def test_the_plan_follows_the_mix():
    rates, budgets = fat.plan(MIX)
    np.testing.assert_allclose(rates, 0.02 + (np.arange(8) + 0.5) * 0.0225)
    assert budgets.tolist() == [2, 3, 4, 4, 5, 6, 7, 8]
    faults = fat.fleet(np.random.SeedSequence(5), rates, (256, 256))
    assert [int(f.sum()) for f in faults] == [int(round(r * 65536)) for r in rates]


@pytest.fixture(scope="module")
def job():
    j = fat.Job({"name": "fat"}, CONF, MIX, 2**35 + 3, rehearse=True, log=lambda m: None)
    j.setup()
    j.window(0.5)
    from repro.core.masking import from_fault_map

    ctxs = [from_fault_map(m) for m in j._maps(j.check_faults)]
    raw = j.trainer.engine.fit_batch(j.params0, ctxs, j.check_budgets, j.trainer._train_batch_fn)
    j.witness = [rtrain.shipped(p, ~f) for p, f in zip(jax.device_get(raw), j.check_faults)]
    j.program_batches = [j.trainer._train_batch_fn(s) for s in (0, 3)]
    j.release()
    return j


def test_the_reference_feeds_the_programs_batches(job):
    for s, got in zip((0, 3), job.program_batches):
        want = rtrain.token_batch(MIX["data"], 256, 32, 2, s)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_the_window_runs_whole_plans(job):
    assert job.plans >= 1 and job.jobs == 8 * job.plans
    assert job.end_to_end()["fat_tokens_per_s"] > 0
    assert job.counts() == (job.jobs, 0)


def test_train_batch_ships_masked_norm_scales_and_embedding(job):
    """The program's fault: ``train_batch`` masks every float leaf of two or
    more dims, so the shipped layer-norm scales and embedding lose their
    entries on faulty PEs. The engine's own trained weights, shipped with
    the GEMM weights masked alone, agree with the reference."""
    program = job.readings(job.check_out)
    witness = job.readings(job.witness)
    bad = {"['embed']", "['layers']['ln1']['scale']", "['layers']['ln2']['scale']"}
    for gaps in program:
        assert max(gaps, key=gaps.get) in bad and max(gaps.values()) > 0.5
        assert max(v for k, v in gaps.items() if k not in bad) < 0.01
    assert max(max(g.values()) for g in witness) < 0.01
    name, value, _ = job.check()[0]
    assert name == "change_gap" and value > 0.5


def test_the_control_trains_and_reads_above_the_engine_path(job):
    control = job.readings([r["shipped"] for r in job.reference(ref.fp8_dot)])
    witness = job.readings(job.witness)
    worst = lambda rs: max(max(g.values()) for g in rs)
    assert 0 < worst(witness) < worst(control) < 0.5
