"""Size a configuration for one TPU v5e without the chip.

    JAX_PLATFORMS=cpu python bench/aot_size.py qwen3-0.6b --slots 16 12 10
    JAX_PLATFORMS=cpu python bench/aot_size.py smollm-135m --fit 2x1 2x2 1x1 --seq 2048

``--slots`` compiles the continuous engine's decode, chunk and packed-admit
programs; ``--fit PxB`` compiles the population FAT engine's fit program
for P members at a batch of B sequences of ``--seq`` tokens each, with a
fault mask per member (the program ``LMFATTrainer.train_batch`` runs). Each
is compiled at the configuration's real widths for a described v5e (no chip
attached), and each program's ``memory_analysis()`` is printed: arguments,
outputs, aliased bytes and temporaries. A program that does not fit is
refused by the TPU compiler here, as it would be on the chip. The bytes
found are recorded in the configuration file under ``aot``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, nargs="+", default=[])
    ap.add_argument("--fit", nargs="+", default=[], metavar="PxB")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--programs", nargs="+", default=["decode", "chunk", "admit256"])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.jobs.serve import program_config
    from repro.models import model as M
    from repro.serve import ContinuousBatchingEngine

    conf = json.loads((ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    cfg = program_config(conf)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev), tree
        )

    params = on_chip(jax.eval_shape(lambda k: M.init_params(cfg, k)[0], jax.random.PRNGKey(0)))

    def report(what: dict, compile_):
        try:
            ma = compile_().memory_analysis()
            row = dict(
                argument=ma.argument_size_in_bytes, output=ma.output_size_in_bytes,
                alias=ma.alias_size_in_bytes, temp=ma.temp_size_in_bytes,
            )
            row["total"] = row["argument"] + row["output"] - row["alias"] + row["temp"]
            print(json.dumps(dict(config=args.config, **what, fits=True, **row)), flush=True)
        except Exception as e:  # the compiler's refusal is the finding
            msg = str(e).splitlines()[0][:300]
            print(json.dumps(dict(config=args.config, **what, fits=False, error=msg)), flush=True)

    for pb in args.fit:
        from repro.data.synthetic import TokenStream
        from repro.train.optimizer import AdamWConfig
        from repro.train.population import PopulationFATEngine

        pop, batch = (int(v) for v in pb.split("x"))
        stream = TokenStream(cfg.vocab_size, args.seq, batch, seed=0)
        tiny = {"tokens": jnp.zeros((1, 8), jnp.int32), "labels": jnp.zeros((1, 8), jnp.int32)}
        eng = PopulationFATEngine(
            loss_fn=lambda p, b, ctx: M.loss_fn(p, b, cfg, ctx, remat="none"),
            opt_cfg=AdamWConfig(learning_rate=1e-3, weight_decay=0.0),
            eval_batches=[tiny], population_size=pop,
        )
        fit = eng._make_fit(lambda s: stream.batch_at(s + 999_983), "fap")
        ok = jax.ShapeDtypeStruct((pop, cfg.array_rows, cfg.array_cols), jnp.float32, sharding=dev)
        budgets = jax.ShapeDtypeStruct((pop,), jnp.int32, sharding=dev)
        report(dict(program="fit", population=pop, batch=batch, seq=args.seq),
               lambda: fit.lower(params, ok, budgets).compile())

    sv = conf["serve"]
    for slots in args.slots:
        maxp = sv["max_pages_per_seq"]
        eng = ContinuousBatchingEngine(
            cfg, params, None, num_slots=slots, page_size=sv["page_size"],
            num_pages=slots * maxp + 1, max_pages_per_seq=maxp,
            prefill_buckets=sv["prefill_buckets"], chunk_size=sv["chunk_size"],
            max_pack=sv["max_pack"],
        )
        cache, cur, active, remaining = on_chip(eng._state_structs())
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
        ctx = on_chip(eng.ctx)
        K, c = eng.max_pack, eng.chunk_size
        lowered = {
            "decode": lambda: eng._sample_decode.lower(
                params, cur, cache, on_chip(jax.random.PRNGKey(0)), ctx,
                jax.ShapeDtypeStruct((), jnp.float32, sharding=dev), active, i32(), remaining),
            "chunk": lambda: eng._prefill_chunk.lower(
                params, i32(1, c), ctx, cache, cur, active, remaining, i32(), i32(maxp),
                i32(c), i32(c), i32(), i32(), i32(),
                jax.ShapeDtypeStruct((), jnp.bool_, sharding=dev)),
            "admit256": lambda: eng._packed_admit.lower(
                params, i32(1, 256), i32(1, 256), i32(1, 256), ctx, cache, cur, active,
                remaining, i32(256), i32(256), i32(K), i32(K), i32(K, maxp), i32(K), i32(K)),
        }
        for name in args.programs:
            report(dict(slots=slots, program=name), lambda: lowered[name]().compile())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
