"""Step-1 wall-clock harness: serial reference vs population FAT engine,
plus the fleet-scale ``--sharded`` mode.

Default mode runs the same resilience sweep (rates x repeats, identical
fault-map grid and identical base params) through the serial and population
engines and reports wall-clock, verifying on the way that the two engines
produce the SAME resilience table — the speedup is only real if the math is
unchanged.

``--sharded`` exercises the repro.fleet subsystem instead: the sweep runs
through ``ShardedPopulationEngine`` on growing "pop" meshes (1, 2, 4, ...
devices — forced host CPU devices unless XLA_FLAGS is already set), so the
JSON reports per-device scaling, re-verifies shard_map↔vmap table equality,
and prints the FleetScheduler's ``wasted_steps`` reduction (LPT vs arrival
order) on a deliberately skewed retraining plan — the run fails unless LPT
strictly reduces waste.

``--mesh POPxMODEL`` (e.g. ``--mesh 4x2``) runs the 2-D fleet-mesh mode:
the sweep runs through the sharded engine on a ``("pop", "model")`` mesh,
re-verifies 2-D↔vmap table equality, and reports PER-MEMBER RESIDENT PARAM
BYTES from the engine's fit output — the run fails unless each member's
resident bytes are <= (its total param bytes / model-axis extent) within
tolerance, i.e. unless member weights are genuinely sharded within pop
slices instead of replicated. ``--population-size auto`` sizes the chunk
width with ``fleet.suggest_population_size`` (per-device memory / member
param+opt bytes).

Companion to benchmarks/kernel_bench.py: where that file guards the Pallas
kernel layer row by row, this one guards the population/fleet training path.
The output is JSON so CI can parse it; ``--smoke`` shrinks the sweep to CI
scale and only checks equivalence, the full run is the perf claim (>= 3x on
CPU at repeats >= 4).

``--tuned`` (composable with any mode) additionally audits the kernel
tuning cache (``repro.tune``): every cached entry is replayed in interpret
mode with tuned vs heuristic blocks on identical inputs and the outputs
must agree to float tolerance — tuning may only change wall-clock, never
the math the resilience tables are built from. The report gains a
``tuning`` section (per-entry diffs plus the capacity planner's per-kernel
VMEM reserve) and the run fails on any numeric mismatch.

Usage:
    PYTHONPATH=src python benchmarks/efat_bench.py [--smoke] [--sharded]
        [--mesh POPxMODEL] [--population-size N|auto] [--devices N]
        [--tuned] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _sweep_config(smoke: bool):
    from repro.core import fault_rate_list

    if smoke:
        sweep = dict(repeats=2, max_steps=80, seed=3)
        rates = fault_rate_list([0.05], max_fr=0.12, max_interval=0.04, step=0.8)
        pretrain = 200
    else:
        # the paper's interesting regime: tight constraint, rates up to 0.3,
        # so high-rate repeats genuinely need tens-to-hundreds of FAT steps
        sweep = dict(repeats=4, max_steps=400, seed=3)
        rates = fault_rate_list([0.04], max_fr=0.3, max_interval=0.05, step=0.5)
        pretrain = 300
    return sweep, rates, pretrain


def _tables_equal(a, b) -> bool:
    import numpy as np

    return bool(
        np.array_equal(a.rates, b.rates)
        and np.array_equal(a.min_steps, b.min_steps)
        and np.array_equal(a.mean_steps, b.mean_steps)
        and np.array_equal(a.max_steps_stat, b.max_steps_stat)
    )


def run_bench(smoke: bool) -> dict:
    from repro.configs import get_arch
    from repro.core.resilience import measure_resilience
    from repro.train.fat_trainer import ClassifierFATTrainer

    sweep, rates, pretrain = _sweep_config(smoke)
    cfg = get_arch("paper-mlp")
    pop_tr = ClassifierFATTrainer(cfg, pretrain_steps=pretrain, eval_batches=2, population_size=32)
    ser_tr = ClassifierFATTrainer(cfg, pretrain_steps=0, eval_batches=2, engine="serial")
    ser_tr.base_params = pop_tr.base_params  # identical starting point
    constraint = pop_tr.baseline_accuracy - (0.05 if smoke else 0.02)

    def sweep_once(trainer, engine):
        t0 = time.time()
        table = measure_resilience(
            trainer, rates, constraint, array_shape=(32, 32), engine=engine, **sweep
        )
        return time.time() - t0, table

    # population first so its compile time is honestly inside its wall-clock
    t_pop, table_pop = sweep_once(pop_tr, None)
    t_ser, table_ser = sweep_once(ser_tr, "serial")

    tables_equal = _tables_equal(table_pop, table_ser)
    speedup = t_ser / t_pop if t_pop > 0 else float("inf")
    return dict(
        mode="smoke" if smoke else "full",
        rates=[round(float(r), 5) for r in rates],
        repeats=sweep["repeats"],
        max_steps=sweep["max_steps"],
        constraint=round(float(constraint), 5),
        rows=[
            dict(name="efat/step1_serial", seconds=round(t_ser, 3), engine="serial"),
            dict(name="efat/step1_population", seconds=round(t_pop, 3), engine="population"),
        ],
        speedup=round(speedup, 2),
        tables_equal=tables_equal,
        max_steps_stat=[float(v) for v in table_pop.max_steps_stat],
    )


def _skewed_plan(max_steps: int, jobs: int = 16) -> list[int]:
    """Interleaved long/short budgets — the regime where arrival-order
    chunking wastes the most vectorized lanes (ROADMAP's 'very skewed
    plans')."""
    long, short = max_steps, max(1, max_steps // 40)
    return [long - 3 * i if i % 2 == 0 else short + i for i in range(jobs)]


def run_sharded_bench(smoke: bool) -> dict:
    import jax

    from repro.configs import get_arch
    from repro.core.resilience import measure_resilience
    from repro.fleet import FleetScheduler
    from repro.launch.mesh import make_pop_mesh
    from repro.train.fat_trainer import ClassifierFATTrainer

    sweep, rates, pretrain = _sweep_config(smoke)
    n_dev = len(jax.devices())
    cfg = get_arch("paper-mlp")
    pop_size = 8 if smoke else 32
    vmap_tr = ClassifierFATTrainer(
        cfg, pretrain_steps=pretrain, eval_batches=2, population_size=pop_size
    )
    constraint = vmap_tr.baseline_accuracy - (0.05 if smoke else 0.02)

    def sweep_once(trainer):
        t0 = time.time()
        table = measure_resilience(
            trainer, rates, constraint, array_shape=(32, 32), **sweep
        )
        return time.time() - t0, table

    t_vmap, table_vmap = sweep_once(vmap_tr)
    rows = [dict(name="efat/step1_population", seconds=round(t_vmap, 3), devices=1)]

    # per-device scaling: 1, 2, 4, ... up to every visible device
    mesh_sizes = [d for d in (1, 2, 4, 8, 16) if d <= n_dev]
    if n_dev not in mesh_sizes:
        mesh_sizes.append(n_dev)
    tables_equal = True
    for d in mesh_sizes:
        tr = ClassifierFATTrainer(
            cfg, pretrain_steps=0, eval_batches=2, engine="sharded",
            population_size=pop_size, engine_kwargs=dict(mesh=make_pop_mesh(d)),
        )
        tr.base_params = vmap_tr.base_params
        t_d, table_d = sweep_once(tr)
        tables_equal = tables_equal and _tables_equal(table_vmap, table_d)
        rows.append(
            dict(name=f"efat/step1_sharded[pop={d}]", seconds=round(t_d, 3), devices=d)
        )

    # scheduler: wasted vectorized lane-steps, LPT vs arrival, skewed plan
    budgets = _skewed_plan(sweep["max_steps"])
    sched_report = FleetScheduler(pop_size, policy="lpt").report(budgets)
    lpt_strictly_reduces = (
        sched_report["wasted_steps"] < sched_report["arrival_wasted_steps"]
    )
    return dict(
        mode="sharded-smoke" if smoke else "sharded-full",
        devices_visible=n_dev,
        rates=[round(float(r), 5) for r in rates],
        repeats=sweep["repeats"],
        max_steps=sweep["max_steps"],
        constraint=round(float(constraint), 5),
        rows=rows,
        tables_equal=tables_equal,
        max_steps_stat=[float(v) for v in table_vmap.max_steps_stat],
        scheduler=dict(
            plan_budgets=budgets,
            lpt_strictly_reduces=lpt_strictly_reduces,
            **sched_report,
        ),
    )


def _parse_mesh(spec: str) -> tuple[int, int]:
    try:
        pop_s, model_s = spec.lower().split("x")
        pop, model = int(pop_s), int(model_s)
    except ValueError:
        raise SystemExit(f"--mesh wants POPxMODEL (e.g. 4x2), got {spec!r}")
    if pop < 1 or model < 1:
        raise SystemExit(f"--mesh extents must be >= 1, got {spec!r}")
    return pop, model


def run_mesh_bench(smoke: bool, mesh_spec: str, population_size: str) -> dict:
    """2-D fleet-mesh mode: pop x model sharded engine vs the vmap engine,
    plus per-member resident param bytes off the fit output."""
    import jax

    from repro.configs import get_arch
    from repro.core.resilience import measure_resilience
    from repro.fleet import suggest_population_size
    from repro.launch.mesh import make_fleet_mesh
    from repro.train.fat_trainer import ClassifierFATTrainer

    sweep, rates, pretrain = _sweep_config(smoke)
    pop_ext, model_ext = _parse_mesh(mesh_spec)
    cfg = get_arch("paper-mlp")
    mesh = make_fleet_mesh(pop_ext, model_ext)
    if population_size == "auto":
        pop_size = suggest_population_size(cfg, mesh)
    else:
        pop_size = int(population_size) if population_size else (8 if smoke else 32)

    vmap_tr = ClassifierFATTrainer(
        cfg, pretrain_steps=pretrain, eval_batches=2, population_size=pop_size
    )
    mesh_tr = ClassifierFATTrainer(
        cfg, pretrain_steps=0, eval_batches=2, engine="sharded",
        population_size=pop_size, engine_kwargs=dict(mesh=mesh),
    )
    mesh_tr.base_params = vmap_tr.base_params
    constraint = vmap_tr.baseline_accuracy - (0.05 if smoke else 0.02)

    def sweep_once(trainer):
        t0 = time.time()
        table = measure_resilience(
            trainer, rates, constraint, array_shape=(32, 32), **sweep
        )
        return time.time() - t0, table

    t_vmap, table_vmap = sweep_once(vmap_tr)
    t_mesh, table_mesh = sweep_once(mesh_tr)
    tables_equal = _tables_equal(table_vmap, table_mesh)

    # resident-memory proof: train a plan and read the engine's accounting
    # of the raw (still member-stacked, still device-resident) fit output
    budgets = _skewed_plan(sweep["max_steps"], jobs=min(mesh_tr.engine.population_size, 16))
    mesh_tr.train_batch(
        [_bench_fault_map(i) for i in range(len(budgets))], budgets
    )
    stats = mesh_tr.engine.last_fit_stats or {}
    resident = stats.get("per_member_resident_bytes", float("inf"))
    total = stats.get("per_member_total_bytes", 0.0)
    # replicated would be == total; sharded is total/model. 5% + 1 KiB of
    # slack absorbs small replicated leaves (biases that don't divide etc.)
    bound = total / model_ext * 1.05 + 1024
    params_sharded = resident <= bound

    return dict(
        mode="mesh-smoke" if smoke else "mesh-full",
        mesh=dict(pop=pop_ext, model=model_ext),
        devices_visible=len(jax.devices()),
        population_size=pop_size,
        population_size_policy=population_size or "fixed",
        rates=[round(float(r), 5) for r in rates],
        repeats=sweep["repeats"],
        max_steps=sweep["max_steps"],
        constraint=round(float(constraint), 5),
        rows=[
            dict(name="efat/step1_population", seconds=round(t_vmap, 3), devices=1),
            dict(
                name=f"efat/step1_mesh[{pop_ext}x{model_ext}]",
                seconds=round(t_mesh, 3), devices=pop_ext * model_ext,
            ),
        ],
        tables_equal=tables_equal,
        max_steps_stat=[float(v) for v in table_vmap.max_steps_stat],
        memory=dict(
            per_member_resident_bytes=resident,
            per_member_total_bytes=total,
            sharded_bound_bytes=round(bound, 1),
            params_sharded_within_pop_slices=params_sharded,
            **{k: stats[k] for k in (
                "chunk_width", "members_per_lane", "pop_extent", "model_extent",
            ) if k in stats},
        ),
    )


def _bench_fault_map(i: int):
    from repro.core import random_fault_map

    return random_fault_map(i, 32, 32, 0.06 + 0.015 * (i % 8))


def run_tuned_check() -> dict:
    """--tuned: prove the tuning cache never changes numerics.

    For every entry in the process-global tuning cache, run the kernel in
    interpret mode with the TUNED blocks and with the HEURISTIC blocks on
    identical inputs (the tuner's own deterministic runners) and compare.
    Block geometry only re-brackets reductions, so the outputs must agree to
    float tolerance — any larger drift means the cache is changing math, and
    the bench exits non-zero. Also reports the capacity planner's per-kernel
    VMEM reserve so ``--population-size auto`` consumers can see what the
    tuned table costs them.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet.capacity import kernel_vmem_reserve
    from repro.tune import KERNELS, get_tuning_cache, parse_key
    from repro.tune.tuner import HEURISTIC_BLOCKS, normalize_blocks

    cache = get_tuning_cache()
    checks = []
    all_match = True
    for key, entry in sorted(cache.entries.items()):
        kernel, shape, dtype_name, _backend = parse_key(key)
        heur = normalize_blocks(kernel, shape, HEURISTIC_BLOCKS[kernel])
        tuned = normalize_blocks(kernel, shape, entry["blocks"])
        runner = KERNELS[kernel].make_runner(shape, jnp.dtype(dtype_name), True)
        a = np.asarray(runner(heur))
        b = np.asarray(runner(tuned))
        atol = 5e-5 if dtype_name == "float32" else 5e-2
        match = bool(np.allclose(a, b, rtol=1e-4, atol=atol))
        all_match = all_match and match
        checks.append(
            dict(
                key=key,
                heuristic_blocks=heur,
                tuned_blocks=tuned,
                max_abs_diff=float(np.max(np.abs(a - b))) if a.size else 0.0,
                numerics_match=match,
            )
        )
    return dict(
        tuning_cache_entries=len(cache.entries),
        tuning_cache_source=cache.source,
        kernel_vmem_reserve_bytes=kernel_vmem_reserve(cache),
        checks=checks,
        numerics_match=all_match,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-scale sweep; equivalence only")
    ap.add_argument(
        "--sharded", action="store_true",
        help="fleet mode: shard_map per-device scaling + scheduler waste report",
    )
    ap.add_argument(
        "--mesh", default=None, metavar="POPxMODEL",
        help="2-D fleet-mesh mode (e.g. 4x2): pop x model sharded engine, "
        "table equality + per-member resident param bytes",
    )
    ap.add_argument(
        "--population-size", default=None,
        help="population chunk width for --mesh: an integer, or 'auto' to "
        "size against per-device memory (fleet.suggest_population_size)",
    )
    ap.add_argument(
        "--devices", type=int, default=8,
        help="forced host CPU device count for --sharded/--mesh "
        "(ignored if XLA_FLAGS is set)",
    )
    ap.add_argument(
        "--tuned", action="store_true",
        help="also verify the kernel tuning cache: tuned vs heuristic blocks "
        "must agree numerically per cached entry (tuning never changes math)",
    )
    ap.add_argument("--out", default=None, help="also write the JSON report to this file")
    args = ap.parse_args(argv)

    if args.sharded and args.mesh:
        ap.error("--sharded and --mesh are separate modes; pass one at a time")
    if (args.sharded or args.mesh) and "XLA_FLAGS" not in os.environ:
        # must happen before the first jax import — all repro imports are lazy
        need = args.devices
        if args.mesh:
            pop_ext, model_ext = _parse_mesh(args.mesh)
            need = max(need, pop_ext * model_ext)
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={need}"
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.mesh:
        report = run_mesh_bench(
            smoke=args.smoke, mesh_spec=args.mesh, population_size=args.population_size
        )
    elif args.sharded:
        report = run_sharded_bench(smoke=args.smoke)
    else:
        report = run_bench(smoke=args.smoke)
    if args.tuned:
        report["tuning"] = run_tuned_check()
    doc = json.dumps(report, indent=2)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc)

    if not report["tables_equal"]:
        print("FAIL: engines disagree on the resilience table", file=sys.stderr)
        return 1
    if args.tuned and not report["tuning"]["numerics_match"]:
        bad = [c["key"] for c in report["tuning"]["checks"] if not c["numerics_match"]]
        print(
            "FAIL: tuned blocks changed kernel numerics for: " + ", ".join(bad),
            file=sys.stderr,
        )
        return 1
    if args.mesh and not report["memory"]["params_sharded_within_pop_slices"]:
        print(
            "FAIL: per-member resident param bytes "
            f"{report['memory']['per_member_resident_bytes']} exceed the sharded "
            f"bound {report['memory']['sharded_bound_bytes']} — member weights are "
            "replicated, not model-sharded",
            file=sys.stderr,
        )
        return 1
    if args.sharded and not report["scheduler"]["lpt_strictly_reduces"]:
        print("FAIL: LPT scheduling did not strictly reduce wasted_steps", file=sys.stderr)
        return 1
    if not args.sharded and not args.mesh and not args.smoke and report["speedup"] < 3.0:
        print(f"FAIL: population speedup {report['speedup']}x below the 3x target", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
