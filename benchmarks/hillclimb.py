"""SPerf hillclimb driver: lower+compile named variants of one dry-run cell
and compare the three roofline terms (requires the 512-device flag, so run
via the CLI below, not inside pytest).

    PYTHONPATH=src:. python -m benchmarks.hillclimb --cell llama3_405b:train_4k \
        --variants baseline,nomask,per_step ...
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
import time

# variant name -> (fault_mode, policy overrides, extra)
VARIANTS = {
    "baseline": dict(fault_mode="fap", overrides={}),
    "nomask": dict(fault_mode="none", overrides={}),
    "per_step": dict(fault_mode="fap", overrides=dict(fault_apply="per_step")),
    "remat_dots": dict(fault_mode="fap", overrides=dict(remat="dots")),
    "remat_none": dict(fault_mode="fap", overrides=dict(remat="none")),
    "no_seqshard": dict(fault_mode="fap", overrides=dict(seq_shard=False)),
    "seqshard": dict(fault_mode="fap", overrides=dict(seq_shard=True)),
    "mb_half": dict(fault_mode="fap", overrides=None, mb_scale=0.5),
    "mb_quarter": dict(fault_mode="fap", overrides=None, mb_scale=0.25),
    "moe_scatter": dict(fault_mode="fap", moe_impl="scatter", overrides={}),
    "per_step+dots": dict(
        fault_mode="fap", overrides=dict(fault_apply="per_step", remat="dots")
    ),
    "per_step+scatter": dict(
        fault_mode="fap", moe_impl="scatter",
        overrides=dict(fault_apply="per_step"),
    ),
    "per_step+dots+mbhalf": dict(
        fault_mode="fap",
        overrides=dict(fault_apply="per_step", remat="dots"), mb_scale=0.5,
    ),
    "remat_dots_mb_quarter": dict(
        fault_mode="fap", overrides=dict(remat="dots"), mb_scale=0.25,
    ),
    "scatter+mbhalf": dict(
        fault_mode="fap", moe_impl="scatter",
        overrides=dict(fault_apply="per_step"), mb_scale=0.5,
    ),
    # attention variants (smollm/hubert-class cells)
    "attn_mixed": dict(fault_mode="fap", overrides=dict(attn_impl="blockwise_mx")),
    "attn_mixed_unroll": dict(
        fault_mode="fap", overrides=dict(attn_impl="blockwise_mx_unroll")
    ),
    "attn_seqshard": dict(
        fault_mode="fap",
        overrides=dict(attn_impl="blockwise_mx_unroll", seq_rule=True),
    ),
    "moe_slotshard": dict(
        fault_mode="fap", moe_impl="scatter",
        overrides=dict(fault_apply="per_step", moe_slot_shard=True),
    ),
    "moe_slotshard_mbhalf": dict(
        fault_mode="fap", moe_impl="scatter",
        overrides=dict(fault_apply="per_step", moe_slot_shard=True), mb_scale=0.5,
    ),
    "attn_unroll_dots_mbq": dict(
        fault_mode="fap",
        overrides=dict(attn_impl="blockwise_mx_unroll", fault_apply="per_step",
                       remat="dots"),
        mb_scale=0.25,
    ),
    "attn_all": dict(
        fault_mode="fap",
        overrides=dict(
            attn_impl="blockwise_mx_unroll", seq_rule=True,
            fault_apply="per_step", remat="dots",
        ),
    ),
}


def run_variant(arch, shape, name, spec, out_dir):
    from repro.configs import SHAPES, get_arch
    from repro.launch.dryrun_lib import run_cell
    from repro.launch.policy import launch_policy

    overrides = spec.get("overrides") or {}
    if spec.get("mb_scale"):
        pol = launch_policy(get_arch(arch), SHAPES[shape])
        overrides = dict(overrides or {},
                         microbatches=max(1, int(pol.microbatches * spec["mb_scale"])))
    t0 = time.time()
    info = run_cell(
        arch, shape,
        fault_mode=spec.get("fault_mode", "fap"),
        moe_impl=spec.get("moe_impl", "einsum"),
        overrides=overrides or None,
        out_dir=None,
    )
    info["variant"] = name
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
        json.dump(info, f, indent=1, default=str)
    return info


def describe(info):
    # the dry run targets a v5e pod: its published peaks (repro.tune.roofline)
    from repro.tune.roofline import V5E

    PEAK, HBM, ICI = V5E.flops, V5E.hbm_bw, V5E.ici_link_bw

    if info.get("status") != "ok":
        return f"FAILED: {info.get('error')}"
    hc = info.get("hlo_cost", {})
    c = hc.get("flops", 0) / PEAK
    m = hc.get("bytes", 0) / HBM
    n = hc.get("collective_bytes", 0) / ICI
    dom = max((c, "compute"), (m, "memory"), (n, "collective"))[1]
    return (
        f"compute={c:9.3f}s memory={m:9.3f}s coll={n:9.3f}s  bound={max(c,m,n):9.3f}s "
        f"dominant={dom}"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, help="arch:shape")
    ap.add_argument("--variants", required=True)
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()
    arch, shape = args.cell.split(":")
    out_dir = os.path.join(args.out, f"{arch}__{shape}")
    for name in args.variants.split(","):
        spec = VARIANTS[name]
        t0 = time.time()
        info = run_variant(arch, shape, name, spec, out_dir)
        print(f"{name:18s} {describe(info)}  [{time.time()-t0:.0f}s]", flush=True)


if __name__ == "__main__":
    main()
