"""Roofline share of the masked GEMM in the ABFT probe.

The probe pushes the canary batch (4 rows plus the checksum row) through
the chip's checksummed masked GEMM on the largest maskable weight, the
first layer's down projection (d_ff x d_model, float32 master weights;
bfloat16 rows and output). Its least time at that logical shape, the
larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth
(``bench/flops.py``), is divided by the device time of each whole
``jit_masked_matmul_checksummed`` program run. The whole program, and not
the Pallas kernel's op alone: XLA stages the weight into on-chip memory in
a copy ahead of the kernel, so the kernel's own op leaves the HBM traffic
out of its time."""

from bench.flops import dims, masked_matmul_flops_bytes

CANARY_ROWS = 4 + 1


def read(run):
    t, peaks = run.trace, run.peaks
    if t is None or peaks is None:
        return None
    spans = t.module_spans("jit_masked_matmul_checksummed")
    if not spans:
        return None
    m = dims(run.job.model)
    r, c = run.conf["array"]
    flops, byts = masked_matmul_flops_bytes(CANARY_ROWS, m["f"], m["d"], r, c,
                                            x_bytes=2, w_bytes=4, out_bytes=2)
    bound = max(flops / peaks.flops, byts / peaks.hbm_bw)
    took = sum(e - s for s, e in spans) * 1e-9
    return 100.0 * bound * len(spans) / took
