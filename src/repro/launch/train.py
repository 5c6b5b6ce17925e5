"""Training launcher CLI.

Runs (or resumes) fault-aware training of any assigned arch on the local
device set, with the same config/policy machinery the dry-run validates at
pod scale. On a real TPU deployment this binary is what every host runs;
here --reduced exercises it end-to-end on CPU.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
        --steps 100 --fault-rate 0.1 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="CPU-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fault-rate", type=float, default=0.0)
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moe-impl", default="einsum", choices=["einsum", "scatter"])
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from repro.configs import get_arch, reduce_config
    from repro.core import from_fault_map, healthy, random_fault_map
    from repro.data.synthetic import TokenStream
    from repro.models import model as M
    from repro.train.loop import LoopConfig, run_training
    from repro.train.optimizer import AdamWConfig, adamw_init, cosine_schedule
    from repro.train.step import make_eval_step, make_jit_train_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    print(f"arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"params={cfg.param_count()/1e6:.1f}M")

    stream = TokenStream(cfg.vocab_size, args.seq, args.batch, seed=0)
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    ocfg = AdamWConfig(
        learning_rate=cosine_schedule(args.lr, warmup=20, total=args.steps)
    )
    # donating (params, opt_state) — safe here because the loop re-binds
    # both from each step's outputs and checkpointing snapshots to host
    # synchronously before the next dispatch
    train_step = make_jit_train_step(
        cfg, ocfg, remat="none", microbatches=args.microbatches,
        moe_impl=args.moe_impl,
    )
    eval_step = jax.jit(make_eval_step(cfg, remat="none"))
    opt = adamw_init(params, ocfg)

    ctx = healthy()
    if args.fault_rate > 0:
        fm = random_fault_map(
            args.fault_seed, cfg.array_rows, cfg.array_cols, args.fault_rate
        )
        ctx = from_fault_map(fm)
        print(f"fault map: rate={fm.fault_rate:.3f} ({fm.num_faults} faulty PEs)")

    eval_batch = stream.batch_at(10_000_000)

    def eval_fn(p):
        return eval_step(p, eval_batch, ctx)

    def on_metrics(step, m):
        keys = ("loss", "accuracy", "eval_loss", "eval_accuracy", "grad_norm", "step_time_s")
        line = " ".join(f"{k}={m[k]:.4f}" for k in keys if k in m)
        print(f"step {step}: {line}", flush=True)

    lc = LoopConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        eval_every=args.eval_every,
        log_every=10,
    )
    t0 = time.time()
    params, opt, state = run_training(
        lc, train_step=train_step, batch_at=stream.batch_at,
        params=params, opt_state=opt, ctx=ctx,
        eval_fn=eval_fn, on_metrics=on_metrics,
    )
    print(f"done: {state.step} steps in {time.time()-t0:.1f}s, "
          f"restarts={state.restarts}, stragglers={len(state.straggler_events)}")


if __name__ == "__main__":
    main()
