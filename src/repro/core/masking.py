"""Fault-context plumbing: how a chip's fault map reaches every matmul.

Model layers never materialize full-weight masks; they call
``fault_linear(x, w, ctx)`` which applies the periodic systolic mask
on the fly (or via the fused Pallas kernel on TPU). ``FaultContext`` is a
pytree so it can be passed through jit/pjit boundaries; the (R, C) healthy
mask is a tiny replicated constant.

Modes
-----
none    : healthy chip — plain matmul, zero overhead.
fap     : Fault-Aware Pruning semantics — weights on faulty PEs are zeroed
          in the forward pass; gradients are masked automatically by the
          chain rule (= FAP+T when training).
pallas  : same semantics, mask fused into the Pallas masked-matmul kernel
          (TPU target; falls back to 'fap' math on CPU backends).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.faults import FaultMap
from repro.core.mapping import masked_weight

# the name scope around each use-site mask (its construction and the
# multiply, not the GEMM) and around the serving engine's load-time premask:
# a compiled program's ops under it are what the engine publishes as its
# programs' fault-mask ops (``mask_ops``), none once the weights are premasked
MASK_SCOPE = "fault_mask"

__all__ = [
    "MASK_SCOPE",
    "FaultContext",
    "fault_linear",
    "fault_einsum",
    "healthy",
    "from_fault_map",
    "stack_contexts",
    "context_leak_reason",
    "is_array_mapped",
]


@jax.tree_util.register_pytree_node_class
@dataclass
class FaultContext:
    """Carries the chip's healthy mask (1=healthy PE, 0=faulty) + mode.

    ``ok`` is normally the single chip's (R, C) mask. A *batched* context
    (built with :func:`stack_contexts`) carries an (N, R, C) stack of N
    chips' masks behind the same static ``mode``; it flows through jit
    boundaries like any pytree but must be consumed under ``jax.vmap`` so
    each traced member sees an ordinary (R, C) mask.
    """

    ok: Optional[jax.Array]  # (R, C) float mask, (N, R, C) stack, or None
    mode: str = "none"  # none | fap | pallas

    def tree_flatten(self):
        return (self.ok,), self.mode

    @classmethod
    def tree_unflatten(cls, mode, children):
        return cls(ok=children[0], mode=mode)

    @property
    def active(self) -> bool:
        return self.mode != "none" and self.ok is not None

    @property
    def population(self) -> Optional[int]:
        """Number of stacked members, or None for a per-chip context."""
        if self.ok is None or self.ok.ndim == 2:
            return None
        return int(self.ok.shape[0])


def healthy() -> FaultContext:
    return FaultContext(ok=None, mode="none")


def from_fault_map(
    fm: Optional[FaultMap], mode: str = "fap", dtype=jnp.float32
) -> FaultContext:
    if fm is None:
        return healthy()
    return FaultContext(ok=jnp.asarray(fm.ok_mask, dtype=dtype), mode=mode)


def stack_contexts(ctxs: Sequence[FaultContext]) -> FaultContext:
    """Stack N per-chip contexts into one batched context.

    The result carries a leading population axis on ``ok`` and the members'
    shared static mode. Healthy members are upcast to an all-ones mask (FAP
    with no faulty PE is exactly the healthy matmul), so a population can mix
    healthy and faulty chips; an all-healthy stack collapses to ``healthy()``.
    """
    if len(ctxs) == 0:
        raise ValueError(
            "stack_contexts: empty population — need at least one FaultContext "
            "(a single-member sequence is fine and stacks to population=1)"
        )
    active = [c for c in ctxs if c.active]
    if not active:
        return healthy()
    modes = {c.mode for c in active}
    if len(modes) != 1:
        raise ValueError(f"cannot stack contexts with mixed modes {sorted(modes)}")
    if any(c.ok.ndim != 2 for c in active):
        raise ValueError("stack_contexts takes per-chip (R, C) contexts, not batched ones")
    shapes = {tuple(c.ok.shape) for c in active}
    if len(shapes) != 1:
        raise ValueError(f"cannot stack contexts with mixed mask shapes {sorted(shapes)}")
    shape, dtype = shapes.pop(), active[0].ok.dtype
    oks = [c.ok if c.active else jnp.ones(shape, dtype) for c in ctxs]
    return FaultContext(ok=jnp.stack(oks), mode=modes.pop())


def context_leak_reason(ctx: Optional[FaultContext]) -> Optional[str]:
    """Static form of the batched-context guard: the reason a context would
    be rejected by the masked-GEMM entry points, or None when it is safe.

    Works on abstract contexts too (``ok`` may be a ShapeDtypeStruct), so
    the program linter (``repro.analysis``) can check an entry point's
    traced signature without executing it; the runtime guard
    ``_require_per_chip`` raises on exactly the same condition.
    """
    if ctx is None or not ctx.active:
        return None
    if ctx.population is not None:
        return (
            f"batched FaultContext (population={ctx.population}) reached a "
            "masked GEMM; consume it under jax.vmap so each member sees an "
            "(R, C) mask"
        )
    if getattr(ctx.ok, "ndim", 2) != 2:
        return f"FaultContext.ok must be (R, C) or (N, R, C), got ndim={ctx.ok.ndim}"
    return None


def _require_per_chip(ctx: FaultContext) -> None:
    reason = context_leak_reason(ctx)
    if reason is not None:
        raise ValueError(reason + " (e.g. via PopulationFATEngine)")


# ---------------------------------------------------------------------------
# The masked-GEMM entry points used by every model layer
# ---------------------------------------------------------------------------


def fault_linear(
    x: jax.Array,
    w: jax.Array,
    ctx: Optional[FaultContext] = None,
    *,
    precision=None,
) -> jax.Array:
    """y = x @ mask(w). ``w`` is (..., d_in, d_out); contraction over -1 of x.

    In 'pallas' mode on a TPU backend the fused kernel is used; everywhere
    else the mask is applied with XLA ops (the paper-faithful formulation).
    Weights are cast to the activation dtype (bf16 compute, fp32 master).
    """
    w = w.astype(x.dtype)
    if ctx is None or not ctx.active:
        return jnp.matmul(x, w, precision=precision)
    _require_per_chip(ctx)
    if ctx.mode == "pallas" and jax.default_backend() == "tpu":
        from repro.kernels.masked_matmul import ops as mm_ops

        return mm_ops.masked_matmul(x, w, ctx.ok)
    with jax.named_scope(MASK_SCOPE):
        w = masked_weight(w, ctx.ok)
    return jnp.matmul(x, w, precision=precision)


def fault_einsum(
    spec: str,
    x: jax.Array,
    w: jax.Array,
    ctx: Optional[FaultContext] = None,
    *,
    precision=None,
) -> jax.Array:
    """Masked einsum for weights whose GEMM view is the last two dims of w
    (e.g. MoE experts '(e,d,f)' — every expert GEMM runs on the same chip,
    hence the same periodic mask)."""
    w = w.astype(x.dtype)
    if ctx is None or not ctx.active:
        return jnp.einsum(spec, x, w, precision=precision)
    _require_per_chip(ctx)
    with jax.named_scope(MASK_SCOPE):
        w = masked_weight(w, ctx.ok)
    return jnp.einsum(spec, x, w, precision=precision)


# ---------------------------------------------------------------------------
# Pytree-level helpers
# ---------------------------------------------------------------------------

# Param leaves that flow through fault_linear/fault_einsum (i.e. execute as
# GEMMs on the systolic array). Embedding lookups, depthwise convs, SSM
# A/D tensors and 1-D scales are NOT array-mapped and must not be masked.
MASKABLE_KEYS = frozenset(
    {
        "wq", "wk", "wv", "wo",  # attention projections
        "wg", "wu", "wd", "wi",  # MLP / expert FFNs
        "router",
        "in_proj", "x_proj", "dt_w", "out_proj",  # SSM GEMMs
        "frontend", "lm_head",
    }
)


def is_array_mapped(path, leaf) -> bool:
    """Whether a param leaf (at its ``tree_map_with_path`` path) runs as a
    GEMM on the array: a weight of two or more dims under a MASKABLE_KEYS key."""
    keys = {getattr(k, "key", None) for k in path}
    return bool(keys & MASKABLE_KEYS) and getattr(leaf, "ndim", 0) >= 2


def mask_selected_params(params: Any, ctx: FaultContext) -> Any:
    """Apply the FAP mask ONCE to every array-mapped weight leaf.

    Because masking is linear and idempotent, pre-masking the params and
    running the model with a healthy context is mathematically identical to
    masking inside every matmul (the paper-faithful formulation) — but it
    touches each weight once per step instead of once per use per
    microbatch. Tied embeddings are intentionally excluded: the lookup must
    see unmasked rows; the tied unembed GEMM keeps its use-site mask.
    """
    if not ctx.active:
        return params
    _require_per_chip(ctx)

    def f(path, leaf):
        if is_array_mapped(path, leaf):
            return masked_weight(leaf, ctx.ok.astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(f, params)


def mask_params(params: Any, ctx: FaultContext, is_mapped=None) -> Any:
    """Apply FAP masks to every array-mapped leaf of a param pytree.

    ``is_mapped(path, leaf) -> bool`` decides which leaves map onto the
    array; default: every float leaf with ndim >= 2.
    """
    if not ctx.active:
        return params
    _require_per_chip(ctx)

    def default_is_mapped(path, leaf):
        return hasattr(leaf, "ndim") and leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating)

    pred = is_mapped or default_is_mapped

    def f(path, leaf):
        if pred(path, leaf):
            return masked_weight(leaf, ctx.ok.astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(f, params)
