"""Fleet subsystem tests (repro.fleet): budget-aware scheduling and device-
sharded population execution (fleet serving: tests/test_serve_continuous.py).

Equivalence contracts pinned here:
* LPT-packed chunks yield bitwise-identical params / steps-to-constraint to
  arrival-order submission (scheduling is pure reordering).
* serial, vmap, and shard_map engines produce identical resilience tables
  and steps-to-constraint (the shard_map check runs in-process on whatever
  devices exist, and in a subprocess on a forced 8-host-device CPU mesh).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import EFAT, EFATConfig, random_fault_map
from repro.core.resilience import measure_resilience
from repro.fleet import (
    FleetScheduler,
    ShardedPopulationEngine,
    suggest_population_size,
)
from repro.fleet.capacity import BYTES_PER_PARAM
from repro.launch.mesh import make_fleet_mesh, make_pop_mesh
from repro.train.fat_trainer import ClassifierFATTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_arch("paper-mlp")


def _leaves_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


@pytest.fixture(scope="module")
def trainers():
    """(lpt, arrival, sharded) ClassifierFATTrainers sharing base params."""
    lpt = ClassifierFATTrainer(CFG, pretrain_steps=300, eval_batches=2, population_size=8)
    arr = ClassifierFATTrainer(
        CFG, pretrain_steps=0, eval_batches=2, population_size=8, schedule="arrival"
    )
    shd = ClassifierFATTrainer(
        CFG, pretrain_steps=0, eval_batches=2, population_size=8, engine="sharded"
    )
    arr.base_params = lpt.base_params
    shd.base_params = lpt.base_params
    return lpt, arr, shd


@pytest.fixture(scope="module")
def fleet():
    rng = np.random.default_rng(7)
    rates = [0.18, 0.03, 0.22, 0.08, 0.12]
    return [random_fault_map(rng, 32, 32, r) for r in rates]


# ---------------------------------------------------------------------------
# FleetScheduler
# ---------------------------------------------------------------------------


def test_scheduler_lpt_packs_by_descending_cost():
    sched = FleetScheduler(population_size=2, policy="lpt").schedule([10, 500, 20, 480])
    assert sched.order == (1, 3, 2, 0)  # descending cost, stable index tiebreak
    assert [c.indices for c in sched.chunks] == [(1, 3), (2, 0)]
    # chunk spans: 500 (with 480 riding 20 wasted), 20 (with 10 riding 10)
    assert sched.chunks[0].span == 500 and sched.chunks[1].span == 20
    assert sched.wasted_steps == (500 - 480) + (20 - 10)


def test_scheduler_lpt_strictly_reduces_waste_on_skewed_plan():
    budgets = [500, 10, 20, 480, 15, 490, 5, 470]  # long/short interleaved
    scheduler = FleetScheduler(population_size=2, policy="lpt")
    rep = scheduler.report(budgets)
    assert rep["wasted_steps"] < rep["arrival_wasted_steps"]
    assert rep["wasted_steps_reduction"] == rep["arrival_wasted_steps"] - rep["wasted_steps"]
    # uniform budgets: nothing to win, nothing to lose
    flat = FleetScheduler(population_size=2).report([100] * 6)
    assert flat["wasted_steps"] == flat["arrival_wasted_steps"] == 0


def test_scheduler_counts_padding_lanes_of_partial_chunks():
    # 3 jobs, width 2: final chunk has one real member + one padding lane
    sched = FleetScheduler(population_size=2, policy="arrival").schedule([50, 50, 40])
    assert [c.indices for c in sched.chunks] == [(0, 1), (2,)]
    assert sched.chunks[1].width == 2
    assert sched.chunks[1].wasted_steps == 40  # the empty lane rides 40 steps
    # a single sub-width submission compiles at its own width, not the max
    small = FleetScheduler(population_size=8).schedule([10, 30])
    assert small.chunks[0].width == 2 and small.chunks[0].wasted_steps == 20
    # sharded engines tile their pop mesh: width rounds up to the mesh size
    # and the extra padding lanes count as waste (they run for real)
    shard = FleetScheduler(population_size=8, width_multiple=8).schedule([100] * 5)
    assert shard.chunks[0].width == 8
    assert shard.chunks[0].wasted_steps == 300  # 3 padding lanes x 100 steps


def test_sharded_trainer_scheduler_counts_mesh_padding(trainers):
    _, _, shd = trainers
    assert shd.scheduler.width_multiple == shd.engine.num_shards


def test_schedule_permute_unpermute_roundtrip():
    sched = FleetScheduler(population_size=3).schedule([5.0, 9.0, 1.0, 7.0])
    seq = ["a", "b", "c", "d"]
    assert sched.unpermute(sched.permute(seq)) == seq
    with pytest.raises(ValueError):
        sched.permute(seq[:2])
    with pytest.raises(ValueError):
        FleetScheduler(population_size=2, policy="bogus")


# ---------------------------------------------------------------------------
# Scheduler invariance on the real training path
# ---------------------------------------------------------------------------


def test_lpt_and_arrival_schedules_bitwise_identical(trainers, fleet):
    """Packing policy changes chunk composition only; every member's
    trajectory — and therefore the shipped params — is bit-for-bit the same."""
    lpt, arr, _ = trainers
    budgets = [30, 5, 25, 10, 7]  # skewed on purpose
    p_lpt = lpt.train_batch(fleet, budgets)
    p_arr = arr.train_batch(fleet, budgets)
    for a, b in zip(p_lpt, p_arr):
        assert _leaves_equal(a, b)
    constraint = lpt.baseline_accuracy - 0.05
    assert lpt.steps_to_constraint_batch(fleet, constraint, 100) == (
        arr.steps_to_constraint_batch(fleet, constraint, 100)
    )


def test_execute_plan_reports_scheduling(trainers, fleet):
    lpt, _, _ = trainers
    ef = EFAT(
        lpt,
        EFATConfig(
            constraint=lpt.baseline_accuracy - 0.06, max_fr=0.25, max_interval=0.06,
            step_ratio=0.8, repeats=2, max_steps=120, m_comparisons=4, k_iterations=1,
        ),
    )
    result = ef.run(fleet)
    assert result.scheduling is not None
    assert result.scheduling["policy"] == "lpt"
    assert result.scheduling["wasted_steps_reduction"] >= 0
    assert "wasted_steps" in result.summary()


# ---------------------------------------------------------------------------
# ShardedPopulationEngine (in-process: mesh over whatever devices exist)
# ---------------------------------------------------------------------------


def test_make_pop_mesh():
    mesh = make_pop_mesh()
    assert mesh.axis_names == ("pop",)
    assert mesh.shape["pop"] == len(jax.devices())
    with pytest.raises(ValueError):
        make_pop_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_pop_mesh(0)


def test_make_pop_mesh_validates_instead_of_raw_reshape():
    """Bad extents get clear ValueErrors naming devices/extents — never a
    raw numpy reshape failure."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_pop_mesh(n + 3)
    with pytest.raises(ValueError, match="integer"):
        make_pop_mesh("four")
    with pytest.raises(ValueError, match=">= 1"):
        make_pop_mesh(-2)


def test_make_fleet_mesh_validation_and_clamping():
    n = len(jax.devices())
    mesh = make_fleet_mesh()  # defaults: every device, model=1
    assert mesh.axis_names == ("pop", "model")
    assert mesh.shape["pop"] == n and mesh.shape["model"] == 1
    # pop=None clamps to the largest clean tiling instead of failing
    if n >= 3:
        clamped = make_fleet_mesh(None, 3)
        assert clamped.shape["pop"] == n // 3
    # explicit extents that don't fit name the numbers in the error
    with pytest.raises(ValueError, match=f"{n + 1} devices"):
        make_fleet_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="model extent"):
        make_fleet_mesh(1, n + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_fleet_mesh(1, 0)
    with pytest.raises(ValueError, match="integer"):
        make_fleet_mesh("4x2")
    with pytest.raises(ValueError, match="axis names"):
        make_fleet_mesh(1, 1, axis_names=("pop",))


def _engine_kwargs_from(engine):
    return dict(
        loss_fn=engine.loss_fn, opt_cfg=engine.opt_cfg,
        eval_batches=[{}], param_axes=engine.param_axes,
    )


def test_sharded_engine_2d_mesh_requires_layout(trainers):
    """A model axis of extent > 1 needs the tensor-parallel layout inputs
    (cfg/mesh_rules + param_axes) and a valid compute mode."""
    _, _, shd = trainers
    dev = jax.devices()[0]
    mesh2 = jax.sharding.Mesh(np.array([dev] * 4).reshape(2, 2), ("pop", "model"))
    kw = _engine_kwargs_from(shd.engine)
    with pytest.raises(ValueError, match="rules"):
        ShardedPopulationEngine(mesh=mesh2, **kw)
    with pytest.raises(ValueError, match="param_axes"):
        ShardedPopulationEngine(mesh=mesh2, cfg=CFG, **{**kw, "param_axes": None})
    with pytest.raises(ValueError, match="compute"):
        ShardedPopulationEngine(cfg=CFG, compute="bogus", **kw)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_device_memory_fallback_only_on_cpu(platform):
    from types import SimpleNamespace

    from repro.fleet.capacity import _device_memory_bytes

    dev = SimpleNamespace(
        platform=platform, device_kind="fake", memory_stats=lambda: {}
    )
    mesh = SimpleNamespace(devices=np.array([dev], dtype=object))
    if platform == "cpu":
        assert _device_memory_bytes(mesh) == 16 << 30
    else:
        with pytest.raises(RuntimeError, match="reports no bytes_limit"):
            _device_memory_bytes(mesh)
        dev.memory_stats = lambda: {"bytes_limit": 123}
        assert _device_memory_bytes(mesh) == 123


def test_suggest_population_size_scales_with_model_axis():
    dev = jax.devices()[0]
    mesh_1d = jax.sharding.Mesh(np.array([dev] * 4), ("pop",))
    mesh_2d = jax.sharding.Mesh(np.array([dev] * 8).reshape(4, 2), ("pop", "model"))
    budget = CFG.param_count() * BYTES_PER_PARAM * 3  # three members per device
    flat = suggest_population_size(CFG, mesh_1d, hbm_bytes=budget, headroom=1.0)
    tp = suggest_population_size(CFG, mesh_2d, hbm_bytes=budget, headroom=1.0)
    assert flat == 3 * 4  # 3 members per lane x 4 lanes
    assert tp == 6 * 4  # model axis halves per-member resident bytes
    assert suggest_population_size(CFG, None, hbm_bytes=budget, headroom=1.0) == 3
    with pytest.raises(ValueError, match="model axis"):
        suggest_population_size(
            get_arch("llama3-405b"), mesh_2d, hbm_bytes=budget
        )
    with pytest.raises(ValueError, match="headroom"):
        suggest_population_size(CFG, mesh_1d, hbm_bytes=budget, headroom=0.0)


def test_sharded_engine_chunks_tile_the_mesh(trainers):
    _, _, shd = trainers
    eng = shd.engine
    assert isinstance(eng, ShardedPopulationEngine)
    assert eng.population_size % eng.num_shards == 0
    for n in (1, eng.num_shards, eng.population_size + 1):
        for _lo, keep, size in eng._chunks(n):
            assert size % eng.num_shards == 0
            assert keep <= size
    with pytest.raises(ValueError):
        ShardedPopulationEngine(
            mesh=make_pop_mesh(axis="rows"), axis_name="pop",
            loss_fn=eng.loss_fn, opt_cfg=eng.opt_cfg, eval_batches=[{}],
        )


def test_sharded_matches_vmap_tables_and_steps(trainers, fleet):
    """shard_map <-> vmap: identical steps-to-constraint and resilience
    tables; params within one float32 ulp-scale tolerance (vmap width
    changes GEMM batching, not member math)."""
    lpt, _, shd = trainers
    constraint = lpt.baseline_accuracy - 0.05
    assert shd.steps_to_constraint_batch(fleet, constraint, 100) == (
        lpt.steps_to_constraint_batch(fleet, constraint, 100)
    )
    rates = [0.06, 0.14, 0.2]
    kw = dict(array_shape=(32, 32), repeats=2, max_steps=100, seed=5)
    t_pop = measure_resilience(lpt, rates, constraint, **kw)
    t_shd = measure_resilience(shd, rates, constraint, **kw)
    assert np.array_equal(t_pop.rates, t_shd.rates)
    assert np.array_equal(t_pop.min_steps, t_shd.min_steps)
    assert np.array_equal(t_pop.mean_steps, t_shd.mean_steps)
    assert np.array_equal(t_pop.max_steps_stat, t_shd.max_steps_stat)
    budgets = [12, 30, 5, 21, 9]
    p_pop = lpt.train_batch(fleet, budgets)
    p_shd = shd.train_batch(fleet, budgets)
    for a, b in zip(p_pop, p_shd):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)
    ev_pop = lpt.evaluate_batch(p_pop, fleet)
    ev_shd = shd.evaluate_batch(p_shd, fleet)
    assert ev_pop == pytest.approx(ev_shd, abs=2e-3)


def test_fleet_mesh_engine_matches_vmap_in_process(trainers, fleet):
    """2-D ("pop", "model") engine over whatever devices exist: identical
    steps-to-constraint / resilience tables to the vmap engine, params to
    ulp tolerance — the same contract the 1-D pop mesh is pinned to. With
    >= 2 devices the model axis is a real extent (the CI fleet job forces
    8); on one device the 1x1 mesh still runs the full 2-D code path."""
    lpt, _, _ = trainers
    n = len(jax.devices())
    model = 2 if n >= 2 and n % 2 == 0 else 1
    mesh = make_fleet_mesh(n // model, model)
    tr = ClassifierFATTrainer(
        CFG, pretrain_steps=0, eval_batches=2, population_size=8,
        engine="sharded", engine_kwargs=dict(mesh=mesh),
    )
    tr.base_params = lpt.base_params
    assert tr.engine.num_shards == n // model  # pop extent, NOT device count
    assert tr.engine.model_size == model
    assert tr.scheduler.width_multiple == n // model
    constraint = lpt.baseline_accuracy - 0.05
    assert tr.steps_to_constraint_batch(fleet, constraint, 100) == (
        lpt.steps_to_constraint_batch(fleet, constraint, 100)
    )
    rates = [0.06, 0.14, 0.2]
    kw = dict(array_shape=(32, 32), repeats=2, max_steps=100, seed=5)
    t_pop = measure_resilience(lpt, rates, constraint, **kw)
    t_2d = measure_resilience(tr, rates, constraint, **kw)
    assert np.array_equal(t_pop.min_steps, t_2d.min_steps)
    assert np.array_equal(t_pop.mean_steps, t_2d.mean_steps)
    assert np.array_equal(t_pop.max_steps_stat, t_2d.max_steps_stat)
    budgets = [12, 30, 5, 21, 9]
    p_pop = lpt.train_batch(fleet, budgets)
    p_2d = tr.train_batch(fleet, budgets)
    for a, b in zip(p_pop, p_2d):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)
    # fit accounting: member params resident sharded model_size-ways
    stats = tr.engine.last_fit_stats
    assert stats is not None and stats["model_extent"] == model
    assert stats["per_member_resident_bytes"] <= (
        stats["per_member_total_bytes"] / model * 1.05 + 1024
    )


# ---------------------------------------------------------------------------
# subprocess: forced 8-host-device CPU mesh (genuine multi-device shard_map)
# ---------------------------------------------------------------------------

_SUB = r"""
import os
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax, numpy as np
from repro.configs import get_arch
from repro.core import random_fault_map
from repro.core.resilience import measure_resilience
from repro.train.fat_trainer import ClassifierFATTrainer

assert len(jax.devices()) == 8
cfg = get_arch('paper-mlp')
pop = ClassifierFATTrainer(cfg, pretrain_steps=250, eval_batches=2, population_size=8)
ser = ClassifierFATTrainer(cfg, pretrain_steps=0, eval_batches=2, engine='serial')
shd = ClassifierFATTrainer(cfg, pretrain_steps=0, eval_batches=2, engine='sharded',
                           population_size=8)
from repro.launch.mesh import make_fleet_mesh
shd2 = ClassifierFATTrainer(cfg, pretrain_steps=0, eval_batches=2, engine='sharded',
                            population_size=8,
                            engine_kwargs=dict(mesh=make_fleet_mesh(4, 2)))
ser.base_params = pop.base_params
shd.base_params = pop.base_params
shd2.base_params = pop.base_params
assert shd.engine.num_shards == 8
assert shd2.engine.num_shards == 4 and shd2.engine.model_size == 2
constraint = pop.baseline_accuracy - 0.05
rates = [0.05, 0.12, 0.2]
kw = dict(array_shape=(32, 32), repeats=2, max_steps=100, seed=11)
t_ser = measure_resilience(ser, rates, constraint, engine='serial', **kw)
t_pop = measure_resilience(pop, rates, constraint, **kw)
t_shd = measure_resilience(shd, rates, constraint, **kw)
t_shd2 = measure_resilience(shd2, rates, constraint, **kw)
fleet = [random_fault_map(i, 32, 32, 0.1 + 0.02 * i) for i in range(5)]
s_ser = ser.steps_to_constraint_batch(fleet, constraint, 100)
s_pop = pop.steps_to_constraint_batch(fleet, constraint, 100)
s_shd = shd.steps_to_constraint_batch(fleet, constraint, 100)
s_shd2 = shd2.steps_to_constraint_batch(fleet, constraint, 100)
budg = [12, 30, 5, 21, 9]
p_pop = pop.train_batch(fleet, budg)
shd2.train_batch(fleet, budg)
mem = shd2.engine.last_fit_stats
# compute='sharded': true tensor-parallel math — float-tolerance equal,
# resident bytes still sharded
tps = ClassifierFATTrainer(cfg, pretrain_steps=0, eval_batches=2, engine='sharded',
                           population_size=8,
                           engine_kwargs=dict(mesh=make_fleet_mesh(4, 2),
                                              compute='sharded'))
tps.base_params = pop.base_params
p_tp = tps.train_batch(fleet, budg)
tp_close = all(
    np.allclose(np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-5)
    for a, b in zip(p_pop, p_tp)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
tp_mem = tps.engine.last_fit_stats
def teq(a, b):
    return bool(np.array_equal(a.max_steps_stat, b.max_steps_stat)
                and np.array_equal(a.min_steps, b.min_steps)
                and np.array_equal(a.mean_steps, b.mean_steps))
print('RESULT', json.dumps(dict(
    devices=len(jax.devices()),
    tables_serial_vmap=teq(t_ser, t_pop),
    tables_vmap_shard=teq(t_pop, t_shd),
    tables_serial_mesh2d=teq(t_ser, t_shd2),
    steps_equal=bool(s_ser == s_pop == s_shd == s_shd2),
    steps=[None if s is None else int(s) for s in s_shd],
    per_member_resident_bytes=mem['per_member_resident_bytes'],
    per_member_total_bytes=mem['per_member_total_bytes'],
    tp_compute_close=bool(tp_close),
    tp_per_member_resident_bytes=tp_mem['per_member_resident_bytes'],
)))
"""


@pytest.mark.slow
def test_serial_vmap_shardmap_identical_on_8_device_mesh():
    """serial <-> vmap <-> 1-D shard_map (pop=8) <-> 2-D shard_map (4x2)
    produce identical resilience tables and steps-to-constraint, and the
    4x2 mesh keeps per-member resident param bytes at total/model-extent
    (member weights sharded within pop slices, not replicated)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)  # the child sets its own device count
    out = subprocess.run(
        [sys.executable, "-c", _SUB], capture_output=True, text=True, env=env,
        timeout=720,
    )
    lines = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert lines, f"no result: {out.stdout[-800:]} {out.stderr[-2000:]}"
    res = json.loads(lines[0][len("RESULT "):])
    assert res["devices"] == 8
    assert res["tables_serial_vmap"], res
    assert res["tables_vmap_shard"], res
    assert res["tables_serial_mesh2d"], res
    assert res["steps_equal"], res
    assert res["per_member_resident_bytes"] <= (
        res["per_member_total_bytes"] / 2 * 1.05 + 1024
    ), res
    assert res["tp_compute_close"], res
    assert res["tp_per_member_resident_bytes"] <= (
        res["per_member_total_bytes"] / 2 * 1.05 + 1024
    ), res
