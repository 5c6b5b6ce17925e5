"""Whole runs at a tiny size on the CPU (``--rehearse``): every cell comes
out correct; with the timed path broken underneath, the check comes out
not correct; the control fails the limits; a run whose recorder drops an
event fails.

The harness's look for a chip is skipped by ``--rehearse``; the rest of a
run is what the chip runs. Limits here are the cells' ``rehearse`` limits
(``bench/limits/<cell>.json``), set from CPU readings at this size."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run

CELLS = [w["name"] for w in json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]]
FAULTY = "smollm-135m.chat.faulty"


def rehearse(cell, capsys, seed=2**35 + 11, tamper=None, extra=()):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", "0",
            "--rehearse", *extra]
    rc = run.main(argv, tamper=tamper)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if rc == 0 else None)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_correct(cell, capsys):
    rc, line = rehearse(cell, capsys)
    assert rc == 0 and line["correct"] and line["rehearsal"]
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    spec, entry, _ = run.load_cell(cell)
    assert set(line["metrics"]) == {m["name"] for m in run.metric_entries(spec, entry, False)}


def _altered_token(job):
    """A token altered where it is produced: the decode step reports the
    next vocabulary id in place of the one it sampled."""
    eng = job.engine
    step = eng._aot[("decode",)]
    vocab = job.cfg.vocab_size

    def altered(*a):
        emitted, *rest = step(*a)
        return ((emitted + 1) % vocab, *rest)

    eng._aot[("decode",)] = altered


def _state_unchanged(job):
    """A step that returns its state unchanged: decode hands back the KV
    cache it was given, so no generated token's keys and values are kept."""
    eng = job.engine
    step = eng._aot[("decode",)]

    def stale(p, cur, cache, *a):
        kept = jax.tree_util.tree_map(jnp.copy, cache)
        emitted, lp, cur, _, *rest = step(p, cur, cache, *a)
        return (emitted, lp, cur, kept, *rest)

    eng._aot[("decode",)] = stale


def _mask_dropped(job):
    """The chip's fault mask left out: every PE computes as healthy."""
    from repro.core.masking import FaultContext

    ctx = job.engine.ctx
    job.engine.set_silicon(FaultContext(ok=jnp.ones_like(ctx.ok), mode=ctx.mode))


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged, _mask_dropped])
def test_a_broken_timed_path_is_not_correct(fault, capsys):
    rc, line = rehearse(FAULTY, capsys, tamper=fault)
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_limits(cell, capsys):
    """The control in the program's place goes through the harness's own
    comparison and comes out not correct."""
    limits = run.load_limits(cell, True)
    rc, line = rehearse(cell, capsys, seed=2**33 + 5, extra=("--control",))
    assert rc == 0 and line["control"] is True
    assert line["correct"] is False
    assert any(line["checks"][k]["value"] > limits[k] for k in limits)


def test_a_run_that_drops_an_event_fails(capsys):
    def tiny_ring(job):
        job.rec.events.capacity = 64

    rc, line = rehearse(FAULTY, capsys, tamper=tiny_ring)
    assert rc == 1 and line is None
