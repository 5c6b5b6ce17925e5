"""The kernel autotuner: lint-gated block search over the four Pallas kernels.

For one ``(kernel, shape, dtype, backend)`` launch the tuner:

1. builds the powers-of-two block lattice (``repro.tune.search``), with
   every raw point **normalized** through the exact ``choose_block``/
   clamping rules the ``ops.py`` wrapper applies — the
   ``analysis.kernelgeom`` launch builders mirror those rules, so the
   normalized blocks are read straight off the built launch;
2. statically accepts or rejects each candidate through the kernel-geometry
   lint (KRN001–KRN004: divisibility, mask-period compatibility, grid
   bounds) plus the *double-buffered* analytic VMEM bound
   (``vmem_footprint(..., double_buffered=True)`` vs ``VMEM_LIMIT_BYTES``)
   — a rejected candidate is never compiled, never launched;
3. times the survivors (jit + warmup + ``block_until_ready``, min over
   ``iters``) under a greedy hillclimb seeded at the heuristic config, with
   recorder spans from :mod:`repro.obs` around every measurement;
4. records the winner with its speedup over the heuristic and its
   achieved-vs-roofline fraction (:mod:`repro.tune.roofline`), as a
   ready-to-commit tuning-cache entry.

Because the heuristic config is always the hillclimb seed, the winner beats
or ties the heuristic by construction — the cache can only speed launches
up. Numerics are untouched: block geometry changes reduction *blocking*
only, which every kernel's tolerance tests already pin.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.analysis.kernelgeom import (
    KernelLaunch,
    check_launch,
    decode_attention_launch,
    flash_attention_launch,
    masked_matmul_launch,
    mamba_scan_launch,
)
from repro.kernels.common import (
    VMEM_LIMIT_BYTES,
    backend_tag,
    is_tpu_backend,
    vmem_footprint,
)
from repro.obs.recorder import NULL_RECORDER
from repro.tune.cache import TuningCache, cache_key
from repro.tune.roofline import kernel_flops_bytes, roofline_fraction
from repro.tune.search import hillclimb, lattice_neighbors, pow2_lattice

__all__ = ["KERNELS", "SHAPE_FIELDS", "TuneResult", "tune_kernel", "tune_many"]


# shape-key fields per kernel, in canonical declaration order
SHAPE_FIELDS = {
    "masked_matmul": ("m", "k", "n", "r", "c"),
    "flash_attention": ("b", "hq", "hkv", "sq", "skv", "d", "causal"),
    "decode_attention": ("b", "hq", "hkv", "skv", "d"),
    "mamba_scan": ("b", "l", "d", "n"),
}

# today's ops.py heuristic defaults — the hillclimb seed and the fallback
HEURISTIC_BLOCKS = {
    "masked_matmul": dict(bm=512, bn=512, bk=512),
    "flash_attention": dict(bq=128, bkv=128),
    "decode_attention": dict(bkv=128),
    "mamba_scan": dict(bd=256, bl=128),
}


def _mm_launch(shape, dtype, blocks) -> KernelLaunch:
    return masked_matmul_launch(
        shape["m"], shape["k"], shape["n"], (shape["r"], shape["c"]),
        bm=blocks["bm"], bn=blocks["bn"], bk=blocks["bk"], dtype=dtype,
    )


def _fa_launch(shape, dtype, blocks) -> KernelLaunch:
    return flash_attention_launch(
        shape["b"], shape["hq"], shape["hkv"], shape["sq"], shape["skv"],
        shape["d"], bq=blocks["bq"], bkv=blocks["bkv"], dtype=dtype,
    )


def _da_launch(shape, dtype, blocks) -> KernelLaunch:
    return decode_attention_launch(
        shape["b"], shape["hq"], shape["hkv"], shape["skv"], shape["d"],
        bkv=blocks["bkv"],
    )


def _ms_launch(shape, dtype, blocks) -> KernelLaunch:
    return mamba_scan_launch(
        shape["b"], shape["l"], shape["d"], shape["n"],
        bd=blocks["bd"], bl=blocks["bl"], dtype=dtype,
    )


def _mm_runner(shape, dtype, interpret):
    from repro.kernels.masked_matmul.ops import masked_matmul

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (shape["m"], shape["k"]), dtype)
    w = jax.random.normal(k2, (shape["k"], shape["n"]), dtype)
    ok = (jax.random.uniform(k3, (shape["r"], shape["c"])) > 0.1).astype(jnp.float32)

    def call(blocks):
        return jax.jit(
            partial(masked_matmul, interpret=interpret, **blocks)
        )(x, w, ok)

    return call


def _fa_runner(shape, dtype, interpret):
    from repro.kernels.flash_attention.ops import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (shape["b"], shape["hq"], shape["sq"], shape["d"]), dtype)
    k = jax.random.normal(ks[1], (shape["b"], shape["hkv"], shape["skv"], shape["d"]), dtype)
    v = jax.random.normal(ks[2], k.shape, dtype)
    causal = bool(shape.get("causal", 1))

    def call(blocks):
        return jax.jit(
            partial(flash_attention, causal=causal, interpret=interpret, **blocks)
        )(q, k, v)

    return call


def _da_runner(shape, dtype, interpret):
    from repro.kernels.decode_attention.ops import decode_attention, quantize_kv

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (shape["b"], shape["hq"], 1, shape["d"]), dtype)
    kc = jax.random.normal(ks[1], (shape["b"], shape["hkv"], shape["skv"], shape["d"]))
    vc = jax.random.normal(ks[2], kc.shape)
    ki, ksc = quantize_kv(kc)
    vi, vsc = quantize_kv(vc)
    valid = shape["skv"]

    def call(blocks):
        return jax.jit(
            partial(decode_attention, interpret=interpret, **blocks)
        )(q, ki, ksc, vi, vsc, valid)

    return call


def _ms_runner(shape, dtype, interpret):
    from repro.kernels.mamba_scan.ops import selective_scan

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(ks[0], (shape["b"], shape["l"], shape["d"]), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], u.shape, dtype))
    a = -jnp.exp(jax.random.normal(ks[2], (shape["d"], shape["n"])))
    b = jax.random.normal(ks[3], (shape["b"], shape["l"], shape["n"]), dtype)
    c = jax.random.normal(ks[4], b.shape, dtype)
    d = jax.random.normal(ks[5], (shape["d"],), dtype)

    def call(blocks):
        return jax.jit(
            lambda *xs: selective_scan(*xs, interpret=interpret, **blocks)[0]
        )(u, dt, a, b, c, d)

    return call


@dataclass(frozen=True)
class KernelSpace:
    """One kernel's tunable space: block params, their lattice axes, the
    geometry builder (mirroring ops.py via analysis.kernelgeom) and the
    measurement runner."""

    params: tuple
    # block param -> shape field giving the lattice's upper bound
    axes: Mapping[str, str]
    # param -> minimum lattice value (TPU sublane floor where relevant)
    floors: Mapping[str, int]
    build_launch: Callable[[Mapping, Any, Mapping], KernelLaunch]
    make_runner: Callable[[Mapping, Any, bool], Callable]
    # positions of each block param inside KernelLaunch.blocks
    launch_slots: Mapping[str, int]


KERNELS: dict[str, KernelSpace] = {
    "masked_matmul": KernelSpace(
        params=("bm", "bn", "bk"),
        axes=dict(bm="m", bn="n", bk="k"),
        floors=dict(bm=8, bn=8, bk=8),
        build_launch=_mm_launch,
        make_runner=_mm_runner,
        launch_slots=dict(bm=0, bn=1, bk=2),
    ),
    "flash_attention": KernelSpace(
        params=("bq", "bkv"),
        axes=dict(bq="sq", bkv="skv"),
        floors=dict(bq=8, bkv=8),
        build_launch=_fa_launch,
        make_runner=_fa_runner,
        launch_slots=dict(bq=1, bkv=2),
    ),
    "decode_attention": KernelSpace(
        params=("bkv",),
        axes=dict(bkv="skv"),
        floors=dict(bkv=8),
        build_launch=_da_launch,
        make_runner=_da_runner,
        launch_slots=dict(bkv=2),
    ),
    "mamba_scan": KernelSpace(
        params=("bd", "bl"),
        axes=dict(bd="d", bl="l"),
        floors=dict(bd=8, bl=8),
        build_launch=_ms_launch,
        make_runner=_ms_runner,
        launch_slots=dict(bd=1, bl=2),
    ),
}


@dataclass
class TuneResult:
    """Outcome of tuning one launch; ``entry`` is the cache-ready record."""

    kernel: str
    shape: dict
    dtype: str
    backend: str
    key: str
    heuristic_blocks: dict
    heuristic_s: float
    best_blocks: dict
    best_s: float
    speedup: float
    roofline_fraction: Optional[float]  # None for interpret-mode times
    vmem_bytes: int
    evaluated: int
    rejected: int
    rejected_configs: list = field(default_factory=list)

    @property
    def entry(self) -> dict:
        return dict(
            blocks=dict(self.best_blocks),
            time_us=round(self.best_s * 1e6, 3),
            heuristic_us=round(self.heuristic_s * 1e6, 3),
            speedup=round(self.speedup, 4),
            roofline_fraction=self.roofline_fraction,
            vmem_bytes=int(self.vmem_bytes),
            backend=self.backend,
            evaluated=self.evaluated,
            rejected=self.rejected,
        )


def normalize_blocks(kernel: str, shape: Mapping[str, int], blocks: Mapping[str, int]) -> dict:
    """Raw lattice point -> the blocks the wrapper would actually launch
    (read back off the kernelgeom launch, which applies the same
    ``choose_block``/clamp rules as ops.py)."""
    space = KERNELS[kernel]
    launch = space.build_launch(shape, jnp.float32, dict(blocks))
    return {p: int(launch.blocks[i]) for p, i in space.launch_slots.items()}


def lint_candidate(
    kernel: str,
    shape: Mapping[str, int],
    dtype: Any,
    blocks: Mapping[str, int],
    *,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
) -> tuple[list, int]:
    """Static accept/reject for one candidate: the KRN001–KRN004 geometry
    lint plus the tuner's conservative double-buffered VMEM bound.
    Returns (findings, double_buffered_vmem_bytes); empty findings = OK."""
    launch = KERNELS[kernel].build_launch(shape, dtype, dict(blocks))
    findings = list(check_launch(launch))
    vmem = vmem_footprint(launch.vmem_blocks, double_buffered=True)
    if vmem > vmem_limit_bytes:
        from repro.analysis.findings import Finding

        findings.append(
            Finding(
                code="KRN002",
                entry_point=launch.kernel,
                subject="vmem",
                message=(
                    f"double-buffered resident blocks need {vmem/2**20:.2f} MiB "
                    f"VMEM (tuner limit {vmem_limit_bytes/2**20:.2f} MiB)"
                ),
                bytes=vmem,
            )
        )
    return findings, vmem


def tune_kernel(
    kernel: str,
    shape: Mapping[str, int],
    dtype: Any = jnp.float32,
    *,
    iters: int = 3,
    max_evals: int = 24,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
    recorder=NULL_RECORDER,
) -> TuneResult:
    """Tune one launch; see the module docstring for the pipeline."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r} (have {sorted(KERNELS)})")
    space = KERNELS[kernel]
    shape = {k: int(v) for k, v in shape.items()}
    missing = [f for f in SHAPE_FIELDS[kernel] if f != "causal" and f not in shape]
    if missing:
        raise ValueError(f"{kernel} shape is missing fields {missing}")
    if interpret is None:
        interpret = not is_tpu_backend()
    backend = backend_tag(interpret)
    dtype_name = jnp.dtype(dtype).name

    lattices = {
        p: pow2_lattice(shape[space.axes[p]], lo=space.floors[p])
        for p in space.params
    }
    runner = space.make_runner(shape, dtype, interpret)

    timed: dict[tuple, float] = {}
    rejected: list[dict] = []

    def score(raw_blocks: Mapping[str, int]) -> Optional[float]:
        blocks = normalize_blocks(kernel, shape, raw_blocks)
        key = tuple(sorted(blocks.items()))
        if key in timed:
            return timed[key]
        findings, _ = lint_candidate(
            kernel, shape, dtype, blocks, vmem_limit_bytes=vmem_limit_bytes
        )
        if findings:
            recorder.count("tune.lint_rejected")
            rejected.append(dict(blocks=blocks, codes=[f.code for f in findings]))
            return None
        label = ",".join(f"{k}={v}" for k, v in sorted(blocks.items()))
        with recorder.timed(f"tune:{kernel}", proc="tune", track=kernel,
                            args=dict(blocks=dict(blocks))):
            fn = lambda: runner(blocks)  # noqa: E731
            jax.block_until_ready(fn())  # compile + warmup outside the clock
            best = float("inf")
            for _ in range(max(1, iters)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                best = min(best, time.perf_counter() - t0)
        recorder.observe(
            f"tune.{kernel}.candidate_s",
            best,
            buckets=(1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0),
        )
        recorder.instant(
            f"tuned:{label}", proc="tune", track=kernel, args=dict(seconds=best)
        )
        timed[key] = best
        return best

    heuristic = normalize_blocks(kernel, shape, HEURISTIC_BLOCKS[kernel])
    heuristic_s = score(heuristic)
    if heuristic_s is None:
        raise ValueError(
            f"heuristic config {heuristic} for {kernel} {shape} fails the "
            "geometry lint — the launch is broken before tuning"
        )

    best, best_s, evals = hillclimb(
        heuristic,
        lambda b: lattice_neighbors(b, lattices),
        score,
        max_evals=max_evals,
    )
    _, best_vmem = lint_candidate(
        kernel, shape, dtype, best, vmem_limit_bytes=vmem_limit_bytes
    )
    flops, byts = kernel_flops_bytes(kernel, shape, dtype)
    return TuneResult(
        kernel=kernel,
        shape=dict(shape),
        dtype=dtype_name,
        backend=backend,
        key=cache_key(kernel, shape, dtype_name, backend),
        heuristic_blocks=heuristic,
        heuristic_s=heuristic_s,
        best_blocks=best,
        best_s=best_s,
        speedup=heuristic_s / best_s if best_s > 0 else float("inf"),
        roofline_fraction=roofline_fraction(
            flops, byts, best_s,
            None if backend == "interpret" else jax.devices()[0].device_kind,
        ),
        vmem_bytes=best_vmem,
        evaluated=len(timed),
        rejected=len(rejected),
        rejected_configs=rejected,
    )


def tune_many(
    cells: list[tuple[str, Mapping[str, int]]],
    *,
    cache: Optional[TuningCache] = None,
    **kwargs,
) -> tuple[list[TuneResult], TuningCache]:
    """Tune a list of (kernel, shape) cells; winners land in ``cache``
    (a fresh one when None). Returns (results, cache)."""
    cache = cache if cache is not None else TuningCache()
    results = []
    for kernel, shape in cells:
        res = tune_kernel(kernel, shape, **kwargs)
        cache.put(res.key, res.entry)
        results.append(res)
    return results, cache
