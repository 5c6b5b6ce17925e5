"""Shared kernel-runtime layer for the Pallas TPU kernels.

Every kernel package (masked_matmul, flash_attention, decode_attention,
mamba_scan) builds on this module instead of re-implementing the same
plumbing four slightly-different ways:

* **Compiler params** — :func:`tpu_compiler_params` builds the
  ``pltpu.CompilerParams`` every ``pallas_call`` takes.
* **Backend autodetection** — :func:`resolve_interpret` turns
  ``interpret=None`` into ``True`` off-TPU so every kernel entry point runs
  on CPU (Pallas interpret mode) without the caller knowing the backend.
* **Block/grid geometry** — :func:`choose_block`, :func:`pad_to_multiple`,
  :func:`pad_axis_to`, :func:`pad_axes_to` and :func:`grid_for` replace the
  four divergent pad/block copies that used to live in the ``ops.py``
  wrappers (one of which silently rejected non-block-multiple shapes).
* **Numerical tolerances** — :func:`dtype_tol` / :func:`assert_close` give
  tests and benchmarks one per-dtype tolerance table instead of ad-hoc
  constants.
"""
from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "tpu_compiler_params",
    "is_tpu_backend",
    "resolve_interpret",
    "backend_tag",
    "choose_block",
    "tuned_block",
    "pad_to_multiple",
    "pad_amount",
    "pad_axis_to",
    "pad_axes_to",
    "grid_for",
    "dtype_tol",
    "assert_close",
    "DEFAULT_TOLS",
    "VMEM_LIMIT_BYTES",
    "MAX_GRID_AXIS",
    "block_bytes",
    "vmem_footprint",
]


# ---------------------------------------------------------------------------
# Compiler params
# ---------------------------------------------------------------------------


def tpu_compiler_params(
    *,
    dimension_semantics: Optional[Sequence[str]] = None,
    **kwargs: Any,
) -> pltpu.CompilerParams:
    """Build the ``compiler_params`` argument for ``pl.pallas_call``."""
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


# ---------------------------------------------------------------------------
# Backend autodetection
# ---------------------------------------------------------------------------


def is_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a tri-state ``interpret`` flag against the active backend.

    ``None`` means "autodetect": compiled on TPU, interpret mode everywhere
    else — so the same kernel call works on a CPU-only host (tests, CI)
    without the caller branching on the backend.
    """
    if interpret is None:
        return not is_tpu_backend()
    return bool(interpret)


def backend_tag(interpret: bool) -> str:
    """The backend component of a tuning-cache key: ``"interpret"`` for
    Pallas interpret mode (any host), else the JAX backend name ("tpu",
    "cpu", ...). Interpret-mode timings are a different machine from
    compiled Mosaic, so their tuned configs must never cross-pollinate."""
    return "interpret" if interpret else str(jax.default_backend())


# ---------------------------------------------------------------------------
# Block sizes, padding, grids
# ---------------------------------------------------------------------------


def choose_block(dim: int, requested: int, *, multiple_of: int = 1) -> int:
    """Clamp a requested block size to ``dim`` and keep it compatible with a
    required period (e.g. a mask period): the result always divides the
    period or is a multiple of it, so periodic index maps stay aligned.
    Blocks below the period that don't divide it snap up to the period;
    incompatible blocks above it are replaced by the period multiple that
    minimizes the padding of ``dim`` (largest such block on ties)."""
    dim, requested, period = int(dim), int(requested), int(multiple_of)
    b = max(1, min(requested, dim))
    if period > 1:
        if b < period:
            if period % b:
                b = period
        elif b % period:
            b = min(
                range(period, b + 1, period),
                key=lambda c: (pad_to_multiple(dim, c) - dim, -c),
            )
    return b


def tuned_block(
    kernel: str,
    shape: Mapping[str, int],
    dtype: Any,
    *,
    interpret: bool,
    defaults: Mapping[str, int],
    overrides: Optional[Mapping[str, Optional[int]]] = None,
) -> dict[str, int]:
    """THE seam between the ``ops.py`` wrappers and the tuning cache.

    Resolution order, per block parameter:

    1. an explicit caller value (``overrides`` entry that is not None) —
       callers who ask for a block get exactly that block, as before;
    2. the process-wide tuning cache (:mod:`repro.tune.cache`) under the
       canonical ``(kernel, shape, dtype, backend)`` key;
    3. the wrapper's heuristic ``defaults`` — so with an empty cache this
       function is an identity on today's behavior, bitwise.

    Returned blocks still flow through ``choose_block``/clamping in the
    wrapper, so even a stale cached config degrades to a *legal* launch
    (the ``kernel_bench.py --tune --check`` CI gate catches it turning
    stale before that). Lookups happen at trace time: a jitted caller
    bakes the blocks of its first trace into the compiled program.
    """
    blocks = {k: int(v) for k, v in defaults.items()}
    from repro.tune.cache import get_tuning_cache  # JAX-free, cycle-free

    hit = get_tuning_cache().lookup_blocks(
        kernel,
        shape,
        jnp.dtype(dtype).name,
        backend_tag(interpret),
    )
    if hit:
        for k in blocks:
            if k in hit:
                blocks[k] = int(hit[k])
    if overrides:
        for k, v in overrides.items():
            if v is not None:
                blocks[k] = int(v)
    return blocks


def pad_to_multiple(n: int, block: int) -> int:
    """Smallest multiple of ``block`` that is >= ``n``."""
    return -(-int(n) // int(block)) * int(block)


def pad_amount(n: int, block: int) -> int:
    """How many trailing elements must be added so ``block`` divides ``n``."""
    return (-int(n)) % int(block)


def pad_axis_to(x: jax.Array, axis: int, target: int, value: float = 0.0) -> jax.Array:
    """Zero-pad (by default) one axis of ``x`` up to ``target`` length."""
    cur = x.shape[axis]
    if cur == target:
        return x
    if cur > target:
        raise ValueError(f"axis {axis} of {x.shape} already exceeds target {target}")
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - cur)
    return jnp.pad(x, widths, constant_values=value)


def pad_axes_to(x: jax.Array, targets: Mapping[int, int], value: float = 0.0) -> jax.Array:
    """Zero-pad several axes of ``x`` at once; no-op axes may be omitted."""
    widths = [(0, 0)] * x.ndim
    changed = False
    for axis, target in targets.items():
        cur = x.shape[axis]
        if cur > target:
            raise ValueError(f"axis {axis} of {x.shape} already exceeds target {target}")
        if cur != target:
            widths[axis] = (0, target - cur)
            changed = True
    return jnp.pad(x, widths, constant_values=value) if changed else x


def grid_for(dims: Sequence[int], blocks: Sequence[int]) -> tuple[int, ...]:
    """Grid extents for ``dims`` tiled by ``blocks`` (dims must divide)."""
    if len(dims) != len(blocks):
        raise ValueError(f"{len(dims)} dims vs {len(blocks)} blocks")
    out = []
    for d, b in zip(dims, blocks):
        if d % b:
            raise ValueError(f"dim {d} not divisible by block {b} ({dims} / {blocks})")
        out.append(d // b)
    return tuple(out)


# ---------------------------------------------------------------------------
# Analytic VMEM accounting (shared by the kernel-geometry lint)
# ---------------------------------------------------------------------------

# Per-core VMEM on current TPU generations (v4/v5: 16 MiB usable scratch).
# A kernel whose resident blocks exceed this fails at Mosaic compile/launch
# time — the geometry lint (repro.analysis.kernelgeom) checks it statically.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024

# Mosaic grid extents are int32; practically, an axis near this bound means
# a degenerate blocking choice long before it overflows.
MAX_GRID_AXIS = 2**31 - 1


def block_bytes(shape: Sequence[int], dtype: Any) -> int:
    """Bytes of one VMEM-resident block of ``shape`` and ``dtype``."""
    n = 1
    for d in shape:
        n *= int(d)
    return n * jnp.dtype(dtype).itemsize


def vmem_footprint(
    blocks: Sequence[tuple], *, double_buffered: bool = False
) -> int:
    """Analytic VMEM footprint of a kernel invocation: the sum of its
    resident blocks — every ``in_specs``/``out_specs`` block plus scratch
    shapes. Entries are ``(shape, dtype)`` or ``(shape, dtype, is_io)``
    where ``is_io`` marks a gridded in/out block the Mosaic pipeline DMAs
    (True for 2-tuples — scratch accumulators should pass False).

    With ``double_buffered=False`` (the lint's historical model) each block
    counts once; ``double_buffered=True`` doubles the DMA'd ``is_io``
    blocks — the bound the autotuner uses, since the pipelined prefetch of
    the next grid step keeps two copies of every in/out block resident.
    """
    total = 0
    for entry in blocks:
        shape, dtype = entry[0], entry[1]
        is_io = bool(entry[2]) if len(entry) > 2 else True
        nbytes = block_bytes(shape, dtype)
        if double_buffered and is_io:
            nbytes *= 2
        total += nbytes
    return total


# ---------------------------------------------------------------------------
# Unified per-dtype tolerance defaults (tests + benchmarks)
# ---------------------------------------------------------------------------

DEFAULT_TOLS: dict[Any, float] = {
    jnp.dtype(jnp.bfloat16): 2e-2,
    jnp.dtype(jnp.float16): 1e-2,
    jnp.dtype(jnp.float32): 2e-5,
    jnp.dtype(jnp.float64): 1e-12,
}


def dtype_tol(dtype: Any, *, atol_scale: float = 10.0) -> tuple[float, float]:
    """(rtol, atol) defaults for comparing a kernel against its reference."""
    rtol = DEFAULT_TOLS.get(jnp.dtype(dtype), 2e-5)
    return rtol, rtol * atol_scale


def assert_close(actual, expected, dtype: Any = None, *, atol_scale: float = 10.0) -> None:
    """np.testing.assert_allclose with the shared per-dtype tolerances.

    Both arrays are compared in float32 so bfloat16 outputs don't lose
    precision a second time inside numpy."""
    if dtype is None:
        dtype = getattr(actual, "dtype", jnp.float32)
    rtol, atol = dtype_tol(dtype, atol_scale=atol_scale)
    np.testing.assert_allclose(
        np.asarray(actual, np.float32),
        np.asarray(expected, np.float32),
        rtol=rtol,
        atol=atol,
    )
