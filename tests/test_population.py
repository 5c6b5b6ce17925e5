"""Population FAT engine tests: serial-vs-population numerical equivalence
(same fault maps + seeds -> identical steps-to-constraint and matching
final metrics/params within the shared per-dtype tolerance), population
chunking invariance, batched-context pytree behavior under jit, Step-1
population submission, and the resilience-table cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduce_config
from repro.core import (
    EFAT,
    EFATConfig,
    FaultContext,
    correlated_family,
    from_fault_map,
    healthy,
    random_fault_map,
    stack_contexts,
)
from repro.core.resilience import measure_resilience
from repro.kernels.common import dtype_tol
from repro.train.fat_trainer import ClassifierFATTrainer, LMFATTrainer
from repro.train.population import PopulationFATEngine, SerialFATEngine, make_fat_engine

CFG = get_arch("paper-mlp")


@pytest.fixture(scope="module")
def trainers():
    """(population, serial) trainers sharing identical base params so any
    divergence comes from the engines, not from pretraining noise."""
    pop = ClassifierFATTrainer(CFG, pretrain_steps=300, eval_batches=2)
    ser = ClassifierFATTrainer(CFG, pretrain_steps=0, eval_batches=2, engine="serial")
    ser.base_params = pop.base_params
    ser.baseline_accuracy = ser.evaluate_params(ser.base_params, healthy())
    return pop, ser


@pytest.fixture(scope="module")
def fleet():
    rng = np.random.default_rng(0)
    rates = [0.02, 0.08, 0.12, 0.18, 0.22]
    return [random_fault_map(rng, 32, 32, r) for r in rates]


# ---------------------------------------------------------------------------
# batched FaultContext
# ---------------------------------------------------------------------------


def test_stack_contexts_batched_pytree_roundtrip_under_jit():
    maps = [random_fault_map(i, 8, 8, 0.2) for i in range(3)]
    stacked = stack_contexts([from_fault_map(fm) for fm in maps])
    assert stacked.population == 3
    assert stacked.ok.shape == (3, 8, 8)
    assert stacked.mode == "fap"
    # flatten/unflatten keeps the mask leaf + static mode
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    assert len(leaves) == 1
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.mode == "fap" and rebuilt.population == 3
    # crosses a jit boundary as a pytree argument
    total = jax.jit(lambda c: c.ok.sum())(stacked)
    assert float(total) == pytest.approx(sum(fm.ok_mask.sum() for fm in maps))
    # vmap over the population axis sees per-chip (R, C) members
    rates = jax.jit(jax.vmap(lambda c: 1.0 - c.ok.mean()))(stacked)
    assert np.allclose(np.asarray(rates), [fm.fault_rate for fm in maps], atol=1e-6)


def test_stack_contexts_empty_population_raises():
    with pytest.raises(ValueError, match="empty population"):
        stack_contexts([])


def test_single_member_population(trainers):
    """A population of ONE is a legal fleet: stacks to population=1 and runs
    through the population engine identically to the serial reference."""
    fm = random_fault_map(5, 32, 32, 0.15)
    stacked = stack_contexts([from_fault_map(fm)])
    assert stacked.population == 1
    assert stacked.ok.shape == (1, 32, 32)
    pop, ser = trainers
    constraint = pop.baseline_accuracy - 0.05
    assert pop.steps_to_constraint_batch([fm], constraint, 100) == (
        ser.steps_to_constraint_batch([fm], constraint, 100)
    )
    p_pop = pop.train_batch([fm], [10])[0]
    p_ser = ser.train_batch([fm], [10])[0]
    rtol, atol = dtype_tol(jnp.float32, atol_scale=100)
    for x, y in zip(jax.tree_util.tree_leaves(p_pop), jax.tree_util.tree_leaves(p_ser)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


def test_stack_contexts_upcasts_healthy_and_rejects_mixed_modes():
    fm = random_fault_map(0, 8, 8, 0.25)
    stacked = stack_contexts([from_fault_map(fm), healthy()])
    assert stacked.population == 2
    assert float(stacked.ok[1].min()) == 1.0  # healthy member = all-ones mask
    assert stack_contexts([healthy(), healthy()]).ok is None
    with pytest.raises(ValueError):
        stack_contexts([from_fault_map(fm, mode="fap"), from_fault_map(fm, mode="pallas")])
    with pytest.raises(ValueError):
        stack_contexts([from_fault_map(fm), stacked])  # no re-stacking


def test_batched_context_rejected_outside_vmap():
    from repro.core import fault_linear

    stacked = stack_contexts([from_fault_map(random_fault_map(i, 8, 8, 0.2)) for i in range(2)])
    with pytest.raises(ValueError, match="vmap"):
        fault_linear(jnp.ones((1, 8)), jnp.ones((8, 8)), stacked)


# ---------------------------------------------------------------------------
# serial vs population equivalence
# ---------------------------------------------------------------------------


def test_steps_to_constraint_population_matches_serial(trainers, fleet):
    pop, ser = trainers
    constraint = pop.baseline_accuracy - 0.05
    got_pop = pop.steps_to_constraint_batch(fleet, constraint, 200)
    got_ser = ser.steps_to_constraint_batch(fleet, constraint, 200)
    assert got_pop == got_ser
    # sanity: the sweep actually spans the interesting regimes
    assert got_pop[0] == 0  # low rate needs no retraining
    assert any(s not in (0, None) for s in got_pop)


def test_train_batch_population_matches_serial(trainers, fleet):
    pop, ser = trainers
    budgets = [25, 40, 10]
    p_pop = pop.train_batch(fleet[:3], budgets)
    p_ser = ser.train_batch(fleet[:3], budgets)
    rtol, atol = dtype_tol(jnp.float32, atol_scale=100)
    for a, b in zip(p_pop, p_ser):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)
    m_pop = pop.evaluate_batch(p_pop, fleet[:3])
    m_ser = ser.evaluate_batch(p_ser, fleet[:3])
    assert m_pop == pytest.approx(m_ser, abs=2e-3)


def test_population_chunking_invariant(trainers, fleet):
    """Chunk size changes how work is submitted, never per-member results."""
    pop, _ = trainers
    constraint = pop.baseline_accuracy - 0.05
    wide = pop.steps_to_constraint_batch(fleet, constraint, 150)
    narrow_engine = make_fat_engine(
        "population",
        loss_fn=pop.engine.loss_fn,
        opt_cfg=pop.opt_cfg,
        eval_batches=pop._evals,
        metric="accuracy",
        eval_every=pop.eval_every,
        population_size=2,
    )
    ctxs = [from_fault_map(fm) for fm in fleet]
    narrow = narrow_engine.steps_to_constraint_batch(
        pop.base_params, ctxs, constraint, 150, pop._probe_batch_fn
    )
    assert wide == narrow
    # fit_batch chunking: padded members never leak into results
    trained = narrow_engine.fit_batch(pop.base_params, ctxs, [8] * len(ctxs), pop._train_batch_fn)
    assert len(trained) == len(fleet)
    ref = pop.engine.fit_batch(pop.base_params, ctxs, [8] * len(ctxs), pop._train_batch_fn)
    for a, b in zip(trained, ref):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-6)


def test_chunk_spans_have_profiler_step_annotations(trainers, fleet, tmp_path):
    """Each fit/probe chunk's recorder span has a twin step annotation on
    the profiler's host line; with no recorder, neither is recorded."""
    import glob

    from jax.profiler import ProfileData

    from repro.obs import Recorder

    pop, _ = trainers
    ctxs = [from_fault_map(fm) for fm in fleet]

    def run(recorder, where):
        eng = make_fat_engine(
            "population", loss_fn=pop.engine.loss_fn, opt_cfg=pop.opt_cfg,
            eval_batches=pop._evals, eval_every=pop.eval_every, population_size=2,
            recorder=recorder,
        )
        with jax.profiler.trace(str(where)):
            eng.fit_batch(pop.base_params, ctxs, [4] * len(ctxs), pop._train_batch_fn)
            eng.steps_to_constraint_batch(pop.base_params, ctxs, 2.0, 10, pop._probe_batch_fn)
        (path,) = glob.glob(str(where / "**" / "*.xplane.pb"), recursive=True)
        return [e.name for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:") for line in plane.lines
                if line.name.startswith("python") for e in line.events
                if e.name in ("fit_chunk", "probe_chunk")]

    rec = Recorder()
    marks = run(rec, tmp_path / "on")
    spans = [e.name for e in rec.event_list() if e.name in ("fit_chunk", "probe_chunk")]
    chunks = -(-len(ctxs) // 2)
    assert sorted(marks) == sorted(spans) == ["fit_chunk"] * chunks + ["probe_chunk"] * chunks
    assert run(None, tmp_path / "off") == []


def test_measure_resilience_engines_agree(trainers):
    """Acceptance: both engines produce the SAME resilience table on
    identical seeds (identical fault-map grid, identical crossings)."""
    pop, ser = trainers
    constraint = pop.baseline_accuracy - 0.06
    rates = [0.05, 0.12, 0.2]
    kw = dict(array_shape=(32, 32), repeats=3, max_steps=150, seed=11)
    t_pop = measure_resilience(pop, rates, constraint, **kw)
    t_ser = measure_resilience(ser, rates, constraint, engine="serial", **kw)
    assert np.array_equal(t_pop.rates, t_ser.rates)
    assert np.array_equal(t_pop.min_steps, t_ser.min_steps)
    assert np.array_equal(t_pop.mean_steps, t_ser.mean_steps)
    assert np.array_equal(t_pop.max_steps_stat, t_ser.max_steps_stat)


def test_execute_plan_population_path(trainers):
    """Step-4 on the batch path: all jobs as one population, all chips
    evaluated in one batch, same bookkeeping as the serial loop."""
    pop, _ = trainers
    fleet = correlated_family(7, 6, 32, 32, base_rate=0.05, idio_rate=0.02)
    ef = EFAT(
        pop,
        EFATConfig(
            constraint=pop.baseline_accuracy - 0.06, max_fr=0.2, max_interval=0.06,
            step_ratio=0.8, repeats=2, max_steps=150, m_comparisons=4, k_iterations=2,
        ),
    )
    result = ef.run(fleet)
    assert sorted(c for link in result.plan.links for c in link) == list(range(6))
    assert set(result.chip_metrics) == set(range(6))
    assert result.satisfied_fraction >= 0.5, result.summary()


# ---------------------------------------------------------------------------
# pallas-mode fault contexts under vmap (reduced-LM population smoke)
# ---------------------------------------------------------------------------


def test_pallas_mode_population_contexts_under_vmap():
    """A population of mode='pallas' contexts runs through the vmap engine:
    on CPU backends the masked GEMM falls back to the fap math, so the
    population eval must equal both the serial reference and the fap-mode
    population bit for bit — pinning that batched pallas contexts are legal
    under vmap (the accelerator path swaps only the GEMM kernel)."""
    cfg = reduce_config(get_arch("qwen3-0.6b"))
    tr = LMFATTrainer(
        cfg, pretrain_steps=5, eval_batches=1, population_size=4,
        batch_size=2, seq_len=16,
    )
    fms = [random_fault_map(i, cfg.array_rows, cfg.array_cols, 0.2) for i in range(3)]
    pallas_ctxs = [from_fault_map(fm, mode="pallas") for fm in fms]
    fap_ctxs = [from_fault_map(fm) for fm in fms]
    stacked = stack_contexts(pallas_ctxs)
    assert stacked.mode == "pallas" and stacked.population == 3

    params = [tr.base_params] * 3
    ev_pallas = tr.engine.evaluate_batch(params, pallas_ctxs)
    ev_fap = tr.engine.evaluate_batch(params, fap_ctxs)
    assert ev_pallas == ev_fap  # same math, different static mode
    ser = SerialFATEngine(
        loss_fn=tr.engine.loss_fn, opt_cfg=tr.opt_cfg,
        eval_batches=tr._evals, metric=tr.metric, eval_every=tr.eval_every,
    )
    ev_ser = ser.evaluate_batch(params, pallas_ctxs)
    assert ev_pallas == pytest.approx(ev_ser, abs=1e-6)
    # a short pallas-mode population fit matches the serial trajectories
    p_pop = tr.engine.fit_batch(tr.base_params, pallas_ctxs, [2, 2, 2], tr._train_batch_fn)
    p_ser = ser.fit_batch(tr.base_params, pallas_ctxs, [2, 2, 2], tr._train_batch_fn)
    rtol, atol = dtype_tol(jnp.float32, atol_scale=100)
    for a, b in zip(p_pop, p_ser):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


def test_masked_matmul_interpret_kernel_under_vmap():
    """The Pallas masked-matmul kernel itself (interpret backend) accepts a
    vmapped mask axis — the exact shape the population engine feeds it on
    accelerator backends."""
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.kernels.masked_matmul.ref import masked_matmul_ref

    key = jax.random.PRNGKey(0)
    kx, kw, km = jax.random.split(key, 3)
    x = jax.random.normal(kx, (4, 16))
    w = jax.random.normal(kw, (16, 24))
    oks = (jax.random.uniform(km, (3, 8, 8)) > 0.25).astype(jnp.float32)
    got = jax.vmap(lambda ok: masked_matmul(x, w, ok, interpret=True))(oks)
    want = jax.vmap(lambda ok: masked_matmul_ref(x, w, ok))(oks)
    rtol, atol = dtype_tol(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# resilience-table cache
# ---------------------------------------------------------------------------


class _StubTrainer:
    """Analytic steps-to-constraint; counts invocations to prove caching."""

    def __init__(self):
        self.calls = 0

    def steps_to_constraint(self, fault_map, constraint, max_steps):
        self.calls += 1
        return min(int(1 + 1000 * fault_map.fault_rate), max_steps)


def test_build_resilience_table_cache_roundtrip(tmp_path):
    fleet = [random_fault_map(i, 16, 16, 0.1) for i in range(3)]
    cache = str(tmp_path / "table.json")
    cfg = EFATConfig(constraint=0.9, repeats=2, max_steps=100, max_fr=0.2)
    tr = _StubTrainer()
    t1 = EFAT(tr, cfg).build_resilience_table(fleet, cache_path=cache)
    assert tr.calls > 0
    first_calls = tr.calls
    # identical config -> served from cache, no new measurements
    t2 = EFAT(tr, cfg).build_resilience_table(fleet, cache_path=cache)
    assert tr.calls == first_calls
    assert np.array_equal(t2.rates, t1.rates)
    assert np.array_equal(t2.max_steps_stat, t1.max_steps_stat)
    assert t2.meta["config"] == t1.meta["config"]
    # config mismatch (different repeats) -> re-measured + cache rewritten
    cfg3 = EFATConfig(constraint=0.9, repeats=3, max_steps=100, max_fr=0.2)
    EFAT(tr, cfg3).build_resilience_table(fleet, cache_path=cache)
    assert tr.calls > first_calls
    t4 = EFAT(_StubTrainer(), cfg3).build_resilience_table(fleet, cache_path=cache)
    assert t4.meta["config"]["repeats"] == 3


# ---------------------------------------------------------------------------
# engine factory
# ---------------------------------------------------------------------------


def test_make_fat_engine_kinds(trainers):
    pop, ser = trainers
    assert isinstance(pop.engine, PopulationFATEngine)
    assert isinstance(ser.engine, SerialFATEngine)
    with pytest.raises(ValueError):
        make_fat_engine("bogus", loss_fn=None, opt_cfg=None, eval_batches=[])
