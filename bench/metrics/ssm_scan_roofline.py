"""Roofline share of the selective scan in chunked prefill.

Over every ``jit__prefill_chunk_fn`` run that lies whole inside the
traced window: the least time of the scan's logical work at that
program's shapes (each Mamba layer scans one ``chunk_size`` chunk of
``d_inner`` channels and ``d_state`` states; ``bench/ssm_flops.py``), the
larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth, divided by
the union of the device intervals of the ops the engine publishes under
the ``ssm_scan`` name scope for that program (``bench/scoped.py``): the
Pallas kernel on a TPU, with its pads and slices. Nothing without a device
trace; an error where the map or the program is missing, or where none of
its scan ops ran in the window."""

from bench.reference.jamba import dims
from bench.scoped import device_trace, scoped_time
from bench.ssm_flops import scan_flops_bytes

PROGRAM = "jit__prefill_chunk_fn"


def read(run):
    if not device_trace(run) or run.peaks is None:
        return None
    covered, _, n_runs, n_ops = scoped_time(run, PROGRAM, "ssm_scan")
    if not n_ops:
        raise RuntimeError(f"no ssm_scan op of {PROGRAM!r} ran in the traced window")
    m = dims(run.job.model)
    flops, byts = scan_flops_bytes(int(run.job.sv["chunk_size"]), m["di"], m["n"])
    bound = m["Ls"] * max(flops / run.peaks.flops, byts / run.peaks.hbm_bw)
    return 100.0 * bound * n_runs / (covered * 1e-9)
