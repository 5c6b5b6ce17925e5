"""Roofline analysis over the dry-run artifacts (deliverable g).

Three terms per (arch x shape) cell on the single-pod mesh, all in seconds
per step, from the compiled dry-run's per-device statistics:

    compute    = HLO_FLOPs_per_device / PEAK_FLOPS
    memory     = HLO_bytes_per_device / HBM_BW
    collective = collective_wire_bytes_per_device / ICI_BW

Plus MODEL_FLOPS (6ND train / 2ND forward) and the useful-compute ratio
MODEL_FLOPS / HLO_FLOPs, which exposes remat recompute, masking overhead,
causal-waste and dispatch overhead. The dominant term is the bottleneck the
SPerf loop iterates on; roofline_fraction = ideal_time / max(terms).
"""
from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass

from repro.configs import SHAPES, get_arch

# the dry run targets a v5e pod: its published per-chip peaks, kept with
# their source in repro.tune.roofline
from repro.tune.roofline import V5E

PEAK_FLOPS, HBM_BW, ICI_BW = V5E.flops, V5E.hbm_bw, V5E.ici_link_bw

CHIPS = {"pod1": 256, "pod2": 512}


@dataclass
class CellRoofline:
    arch: str
    shape: str
    kind: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_per_dev: float
    hlo_flops_per_dev: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def ideal_s(self) -> float:
        return self.model_flops_per_dev / PEAK_FLOPS

    @property
    def roofline_fraction(self) -> float:
        return self.ideal_s / self.bound_time if self.bound_time > 0 else 0.0

    @property
    def useful_compute_ratio(self) -> float:
        return (
            self.model_flops_per_dev / self.hlo_flops_per_dev
            if self.hlo_flops_per_dev
            else 0.0
        )


def model_flops_per_device(arch: str, shape_name: str, chips: int) -> float:
    """6ND for training, 2ND for forward passes; MoE counts active params;
    decode processes 1 token per sequence."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n * shape.global_batch
    return total / chips


def load_cell(path: str):
    info = json.load(open(path))
    if info.get("status") != "ok":
        return None
    tag = "pod2" if info.get("multi_pod") else "pod1"
    chips = CHIPS[tag]
    hc = info.get("hlo_cost") or {}
    ca = info.get("cost_analysis", {})
    # loop-aware HLO walk (repro.launch.hlo_cost); entry-level XLA numbers
    # as fallback (undercount while bodies)
    flops = float(hc.get("flops") or ca.get("flops", 0.0))
    byts = float(hc.get("bytes") or ca.get("bytes accessed", 0.0))
    coll = float(
        hc.get("collective_bytes")
        or info.get("collectives", {}).get("total_bytes", 0.0)
    )
    return CellRoofline(
        arch=info["arch"],
        shape=info["shape"],
        kind=info.get("kind", SHAPES[info["shape"]].kind),
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=coll / ICI_BW,
        model_flops_per_dev=model_flops_per_device(info["arch"], info["shape"], chips),
        hlo_flops_per_dev=flops,
    ), info


def analyze(dryrun_dir: str = "experiments/dryrun", tag: str = "pod1"):
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, f"*__{tag}.json"))):
        got = load_cell(path)
        if got is None:
            continue
        cell, info = got
        rows.append(cell)
    return rows


def table(rows) -> str:
    hdr = (
        f"{'arch':28s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
        f"{'collect_s':>10s} {'dominant':>10s} {'roofline%':>9s} {'useful%':>8s}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:28s} {r.shape:12s} {r.compute_s:10.4g} {r.memory_s:10.4g} "
            f"{r.collective_s:10.4g} {r.dominant:>10s} "
            f"{100 * r.roofline_fraction:8.1f}% {100 * r.useful_compute_ratio:7.1f}%"
        )
    return "\n".join(lines)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--tag", default="pod1")
    ap.add_argument("--csv", default=None)
    args = ap.parse_args()
    rows = analyze(args.dir, args.tag)
    print(table(rows))
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(
                "arch,shape,kind,compute_s,memory_s,collective_s,dominant,"
                "roofline_fraction,useful_compute_ratio,model_flops_dev,hlo_flops_dev\n"
            )
            for r in rows:
                f.write(
                    f"{r.arch},{r.shape},{r.kind},{r.compute_s},{r.memory_s},"
                    f"{r.collective_s},{r.dominant},{r.roofline_fraction},"
                    f"{r.useful_compute_ratio},{r.model_flops_per_dev},{r.hlo_flops_per_dev}\n"
                )
        print(f"\nwrote {args.csv}")


if __name__ == "__main__":
    main()
