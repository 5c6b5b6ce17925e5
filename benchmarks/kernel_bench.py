"""Micro-benchmarks of the fault-masking substrate on the host backend.

These time the XLA (non-Pallas) paths — the Pallas kernels target TPU and
are validated for correctness in interpret mode; their perf claims are
made structurally in EXPERIMENTS.md SPerf from the lowered HLO.
Here we measure the paper-relevant CPU-visible deltas:
  * masked vs unmasked matmul (the FAP overhead the fused kernel removes)
  * blockwise vs dense attention at long sequence (memory-safe prefill)
  * per-kernel before/after regression rows for all four Pallas kernels
    (reference path vs the kernel path through the shared runtime layer;
    on CPU the kernel path runs in interpret mode, so the timing is a
    correctness/regression signal, not a perf claim)

``--tune`` runs the kernel autotuner (``repro.tune``) over the committed
shape suite instead and emits ``benchmarks/BENCH_kernels.json`` — the
committed perf-trajectory snapshot (per-cell best config, speedup over the
heuristic, achieved-vs-roofline fraction). ``--tune --check`` gates CI:

  * every committed cell must re-tune to a tuned/heuristic wall-clock ratio
    no more than 10% (plus a small absolute epsilon) worse than the
    committed ratio — ratios, not raw seconds, so the gate is portable
    across runner hardware;
  * every entry in the committed tuning cache
    (``src/repro/tune/default_cache.json``) must still pass the
    kernel-geometry lint — a kernel change that invalidates a cached
    config fails here, not at launch time.

``--tune --write-cache`` additionally rewrites the committed default cache
with the fresh winners (run it with ``--out`` when regenerating both
artifacts after a kernel or suite change).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import from_fault_map, healthy, random_fault_map
from repro.core.masking import fault_linear
from repro.kernels.common import dtype_tol, is_tpu_backend
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import attention_impl

Row = tuple[str, float, str]


def _time(fn, *args, iters=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / iters


def bench_masked_matmul_overhead() -> list[Row]:
    m, k, n = 512, 1024, 1024
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    fm = random_fault_map(0, 256, 256, 0.1)
    ctx_h, ctx_f = healthy(), from_fault_map(fm)
    f_h = jax.jit(lambda x, w: fault_linear(x, w, ctx_h))
    f_m = jax.jit(lambda x, w: fault_linear(x, w, ctx_f))
    t_h = _time(f_h, x, w)
    t_m = _time(f_m, x, w)
    return [
        ("kernel/matmul_healthy", t_h * 1e6, f"{2*m*k*n/t_h/1e9:.1f} GFLOP/s"),
        (
            "kernel/matmul_fap_masked", t_m * 1e6,
            f"overhead {100*(t_m-t_h)/t_h:.0f}% (removed by fused Pallas kernel on TPU)",
        ),
    ]


def bench_attention_impls() -> list[Row]:
    b, hq, hkv, s, d = 1, 8, 2, 2048, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    f_d = jax.jit(lambda q, k, v: attention_impl(q, k, v, causal=True, window=None, impl="dense"))
    f_b = jax.jit(lambda q, k, v: attention_impl(q, k, v, causal=True, window=None, impl="blockwise"))
    f_w = jax.jit(lambda q, k, v: attention_impl(q, k, v, causal=True, window=256, impl="blockwise"))
    t_d = _time(f_d, q, k, v, iters=3)
    t_b = _time(f_b, q, k, v, iters=3)
    t_w = _time(f_w, q, k, v, iters=3)
    return [
        ("kernel/attn_dense_2k", t_d * 1e6, "materializes S^2 scores"),
        ("kernel/attn_blockwise_2k", t_b * 1e6, f"flat memory, {t_b/t_d:.2f}x dense time"),
        ("kernel/attn_swa_blockwise_2k", t_w * 1e6, f"O(S*w): {t_w/t_b:.2f}x of full blockwise"),
    ]


# ---------------------------------------------------------------------------
# Per-kernel before/after regression harness (all four Pallas kernels)
# ---------------------------------------------------------------------------


def _regression_row(name: str, ref_fn, kernel_fn, ref_out, kernel_out) -> list[Row]:
    """Time the reference ('before') and kernel ('after') paths and check
    the kernel against the oracle with the shared tolerance table."""
    rtol, atol = dtype_tol(jnp.float32, atol_scale=50)
    err = float(
        np.max(
            np.abs(
                np.asarray(kernel_out, np.float32) - np.asarray(ref_out, np.float32)
            )
        )
    )
    ok = bool(
        np.allclose(
            np.asarray(kernel_out, np.float32),
            np.asarray(ref_out, np.float32),
            rtol=rtol,
            atol=atol,
        )
    )
    t_ref = _time(ref_fn, iters=3)
    t_ker = _time(kernel_fn, iters=3)
    mode = "compiled" if is_tpu_backend() else "interpret"
    return [
        (f"kernel/{name}_ref", t_ref * 1e6, "reference (before)"),
        (
            f"kernel/{name}_pallas",
            t_ker * 1e6,
            f"{mode}; max|err|={err:.2e} {'OK' if ok else 'REGRESSION'}",
        ),
    ]


def bench_kernel_regressions() -> list[Row]:
    """Before/after rows for masked_matmul, flash_attention,
    decode_attention and mamba_scan. Shapes are deliberately tiny: off-TPU
    the kernel body runs in the Pallas interpreter, which is orders of
    magnitude slower than XLA — this harness guards numerics and the shared
    runtime plumbing, and doubles as the perf harness on a real TPU."""
    rows: list[Row] = []
    key = jax.random.PRNGKey(0)

    # masked_matmul
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.kernels.masked_matmul.ref import masked_matmul_ref

    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (32, 64))
    w = jax.random.normal(k2, (64, 48))
    ok = (jax.random.uniform(k3, (16, 16)) > 0.1).astype(jnp.float32)
    ref_fn = jax.jit(lambda: masked_matmul_ref(x, w, ok))
    ker_fn = jax.jit(
        lambda: masked_matmul(x, w, ok, bm=32, bn=32, bk=32, interpret=not is_tpu_backend())
    )
    rows += _regression_row("masked_matmul", ref_fn, ker_fn, ref_fn(), ker_fn())

    # flash_attention
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    kk = jax.random.normal(ks[1], (1, 2, 64, 32))
    v = jax.random.normal(ks[2], (1, 2, 64, 32))
    ref_fn = jax.jit(lambda: attention_ref(q, kk, v, causal=True, window=None))
    ker_fn = jax.jit(
        lambda: flash_attention(
            q, kk, v, causal=True, bq=32, bkv=32, interpret=not is_tpu_backend()
        )
    )
    rows += _regression_row("flash_attention", ref_fn, ker_fn, ref_fn(), ker_fn())

    # decode_attention (int8 KV)
    from repro.kernels.decode_attention.ops import decode_attention, quantize_kv
    from repro.kernels.decode_attention.ref import decode_attention_ref

    ks = jax.random.split(key, 3)
    q1 = jax.random.normal(ks[0], (1, 2, 1, 32))
    kc = jax.random.normal(ks[1], (1, 2, 128, 32))
    vc = jax.random.normal(ks[2], (1, 2, 128, 32))
    ki, ksc = quantize_kv(kc)
    vi, vsc = quantize_kv(vc)
    ref_fn = jax.jit(
        lambda: decode_attention_ref(q1, ki, ksc, vi, vsc, kv_valid_len=100)
    )
    ker_fn = jax.jit(
        lambda: decode_attention(
            q1, ki, ksc, vi, vsc, 100, bkv=64, interpret=not is_tpu_backend()
        )
    )
    rows += _regression_row("decode_attention", ref_fn, ker_fn, ref_fn(), ker_fn())

    # mamba selective scan
    from repro.kernels.mamba_scan.ops import selective_scan
    from repro.kernels.mamba_scan.ref import selective_scan_ref

    ks = jax.random.split(key, 6)
    u = jax.random.normal(ks[0], (1, 32, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 32, 16)))
    a = -jnp.exp(jax.random.normal(ks[2], (16, 4)))
    bb = jax.random.normal(ks[3], (1, 32, 4))
    c = jax.random.normal(ks[4], (1, 32, 4))
    dd = jax.random.normal(ks[5], (16,))
    ref_fn = jax.jit(lambda: selective_scan_ref(u, dt, a, bb, c, dd)[0])
    ker_fn = jax.jit(
        lambda: selective_scan(
            u, dt, a, bb, c, dd, bd=16, bl=16, interpret=not is_tpu_backend()
        )[0]
    )
    rows += _regression_row("mamba_scan", ref_fn, ker_fn, ref_fn(), ker_fn())

    return rows


ALL = [bench_masked_matmul_overhead, bench_attention_impls, bench_kernel_regressions]


def print_rows(fns) -> int:
    """Shared ``name,us_per_call,derived`` CSV printer (also used by
    benchmarks/run.py so the two outputs cannot drift). A phase that raises
    prints its traceback and no row; returns how many phases failed."""
    import traceback

    print("name,us_per_call,derived")
    failed = 0
    for fn in fns:
        try:
            for name, us, derived in fn():
                print(f'{name},{us:.1f},"{derived}"', flush=True)
        except Exception:
            traceback.print_exc()
            print(f"FAIL: {fn.__name__}", file=sys.stderr, flush=True)
            failed += 1
    return failed


# ---------------------------------------------------------------------------
# --tune: the committed kernel-autotuning suite + perf-trajectory snapshot
# ---------------------------------------------------------------------------

SNAPSHOT_VERSION = 1
BENCH_PATH = "benchmarks/BENCH_kernels.json"

# The committed shape suite: small enough that interpret mode finishes in CI
# minutes, non-trivial enough that the heuristic is NOT always the winner
# (heuristic blocks smaller than the axis leave grid steps on the table).
TUNE_SUITE: list[tuple[str, dict]] = [
    ("masked_matmul", dict(m=64, k=64, n=64, r=16, c=16)),
    ("masked_matmul", dict(m=128, k=128, n=128, r=32, c=32)),
    ("flash_attention", dict(b=1, hq=2, hkv=1, sq=256, skv=256, d=32, causal=1)),
    ("decode_attention", dict(b=1, hq=2, hkv=2, skv=512, d=32)),
    ("mamba_scan", dict(b=1, l=256, d=64, n=8)),
]

# --check tolerance on the tuned/heuristic wall-clock ratio: machine noise
# moves both numerators and denominators, so a relative band + small
# absolute epsilon holds across runner generations.
RATIO_SLACK_REL = 1.10
RATIO_SLACK_ABS = 0.05


def run_tune(iters: int = 3, max_evals: int = 16):
    """Tune the committed suite; returns (snapshot_dict, results, cache)."""
    from repro.kernels.common import backend_tag, is_tpu_backend
    from repro.obs.recorder import Recorder
    from repro.tune import set_tuning_cache, tune_many, TuningCache

    # tune against heuristics only — a stale global cache must not seed
    # (or contaminate) the measurement of what the heuristic costs
    prev = set_tuning_cache(TuningCache())
    rec = Recorder()
    try:
        results, cache = tune_many(
            TUNE_SUITE, iters=iters, max_evals=max_evals, recorder=rec
        )
    finally:
        set_tuning_cache(prev)

    cells = {}
    for res in results:
        cells[res.key] = dict(
            kernel=res.kernel,
            shape=res.shape,
            dtype=res.dtype,
            heuristic=dict(
                blocks=res.heuristic_blocks, us=round(res.heuristic_s * 1e6, 1)
            ),
            tuned=dict(blocks=res.best_blocks, us=round(res.best_s * 1e6, 1)),
            ratio=round(res.best_s / res.heuristic_s, 4),
            speedup=round(res.speedup, 4),
            roofline_fraction=res.roofline_fraction,
            vmem_bytes=res.vmem_bytes,
            evaluated=res.evaluated,
            rejected=res.rejected,
        )
    snapshot = dict(
        version=SNAPSHOT_VERSION,
        backend=backend_tag(not is_tpu_backend()),
        iters=iters,
        max_evals=max_evals,
        tune_spans_recorded=len(rec.event_list()),
        cells=cells,
    )
    return snapshot, results, cache


def check_tune(snapshot: dict, committed_path: str) -> list[str]:
    """CI gate: fresh snapshot vs the committed one + relint of the
    committed tuning cache. Returns a list of failure messages."""
    from repro.tune.cache import DEFAULT_CACHE_PATH, TuningCache, parse_key
    from repro.tune.tuner import lint_candidate

    failures: list[str] = []
    try:
        committed = json.load(open(committed_path))
    except (OSError, json.JSONDecodeError) as e:
        return [f"cannot read committed snapshot {committed_path}: {e}"]
    if committed.get("version") != SNAPSHOT_VERSION:
        return [f"committed snapshot version {committed.get('version')} != {SNAPSHOT_VERSION}"]

    fresh_cells = snapshot["cells"]
    for key, cell in committed.get("cells", {}).items():
        fresh = fresh_cells.get(key)
        if fresh is None:
            failures.append(
                f"committed cell {key} missing from the fresh tune — suite "
                "changed? regenerate with --tune --out " + committed_path
            )
            continue
        bound = cell["ratio"] * RATIO_SLACK_REL + RATIO_SLACK_ABS
        if fresh["ratio"] > bound:
            failures.append(
                f"{key}: tuned/heuristic ratio regressed to {fresh['ratio']:.3f} "
                f"(committed {cell['ratio']:.3f}, bound {bound:.3f}) — the tuner "
                "no longer finds the committed win"
            )

    # every committed cache entry must still be a lintable launch
    cache = TuningCache.load(DEFAULT_CACHE_PATH)
    for key, entry in cache.entries.items():
        kernel, shape, dtype, _backend = parse_key(key)
        findings, _ = lint_candidate(kernel, shape, jnp.dtype(dtype), entry["blocks"])
        if findings:
            codes = ",".join(f.code for f in findings)
            failures.append(
                f"cached config {key} -> {entry['blocks']} now fails the "
                f"kernel-geometry lint ({codes}) — a kernel change invalidated "
                "it; re-run --tune --write-cache"
            )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tune", action="store_true", help="run the autotuner suite")
    ap.add_argument("--check", action="store_true",
                    help="with --tune: gate against the committed snapshot")
    ap.add_argument("--write-cache", action="store_true",
                    help="with --tune: rewrite src/repro/tune/default_cache.json")
    ap.add_argument("--out", default=None,
                    help=f"with --tune: write the snapshot (canonical: {BENCH_PATH})")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--max-evals", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if not args.tune:
        return 1 if print_rows(ALL) else 0

    snapshot, results, cache = run_tune(iters=args.iters, max_evals=args.max_evals)
    for res in results:
        print(
            f"{res.kernel:18s} {res.heuristic_blocks} {res.heuristic_s*1e6:9.1f}us"
            f" -> {res.best_blocks} {res.best_s*1e6:9.1f}us  x{res.speedup:.2f}"
            f"  roofline {res.roofline_fraction}  ({res.evaluated} timed,"
            f" {res.rejected} lint-rejected)",
            file=sys.stderr, flush=True,
        )
    # gate BEFORE writing: --check always compares against the *committed*
    # snapshot, even when --out points at the same file
    failures: list[str] = []
    if args.check:
        failures = check_tune(snapshot, BENCH_PATH)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(snapshot, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}", file=sys.stderr)
    if args.write_cache:
        from repro.tune.cache import DEFAULT_CACHE_PATH

        cache.save(DEFAULT_CACHE_PATH)
        print(f"wrote {DEFAULT_CACHE_PATH} ({len(cache)} entries)", file=sys.stderr)

    if args.check:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        if failures:
            return 1
        print(
            f"tune check OK: {len(snapshot['cells'])} cells within "
            f"{RATIO_SLACK_REL:.0%}+{RATIO_SLACK_ABS} of the committed ratios; "
            "cached configs lint-clean",
            file=sys.stderr,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
