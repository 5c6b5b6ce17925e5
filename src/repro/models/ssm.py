"""Mamba-1 selective-SSM block (falcon-mamba, hymba's SSM branch, Jamba's
Mamba layers).

Train/prefill uses the selective scan (Pallas kernel on TPU, lax.scan
reference elsewhere); decode carries (conv_state, ssm_state) — O(1) memory
in sequence length, which is what makes the long_500k cells runnable. A
prompt streamed in chunks continues each chunk from the state the one
before it left.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.masking import FaultContext, fault_linear
from repro.kernels.mamba_scan.ops import selective_scan, selective_step
from repro.launch.sharding import shard_activation
from repro.models.layers import rms_norm

Array = jax.Array


class SSMCache(NamedTuple):
    conv: Array  # (B, K-1, d_inner) float32 last inputs to the causal conv
    h: Array  # (B, d_inner, N) float32 SSM state


def _causal_conv(x: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv via shift-and-add (K is tiny, typically 4).

    x: (B, L, D); w: (K, D); b: (D,). Elementwise formulation shards
    cleanly (no conv op in the HLO)."""
    k = w.shape[0]
    w = w.astype(x.dtype)
    b = b.astype(x.dtype)
    out = x * w[-1][None, None, :]
    for i in range(1, k):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1], :]
        out = out + shifted * w[k - 1 - i][None, None, :]
    return out + b[None, None, :]


MAMBA_SCOPE = "mamba"  # jax.named_scope of every Mamba mixer
SCAN_SCOPE = "ssm_scan"  # ... and of its selective scan (kernel, reference or step)


def ssm_block(
    p: dict,
    x: Array,  # (B, S, d_model)
    cfg,
    ctx: FaultContext,
    *,
    cache: Optional[SSMCache] = None,
    build_cache: bool = False,
    valid: Optional[Array] = None,
):
    """Returns (y (B, S, d_model), new_cache).

    ``cache`` is the state to continue from: a one-token step (decode) runs
    ``selective_step``, a longer chunk the scan from the cache's ``h``;
    without one the state starts at zero. With a cache or ``build_cache``
    the state after the sequence comes back (conv-input tail and SSM
    state, both float32). ``valid`` ((B, S) bool, a prefix of each row)
    marks the real tokens of a right-padded row: the pad's inputs and time
    steps are zeroed (``h <- exp(0 * A) * h + 0 = h``), so the state that
    comes back is the one after the last real token.

    With ``cfg.ssm_inner_norm`` (Jamba) the dt, B and C streams are
    RMS-normed before use."""
    b, s, _ = x.shape
    di, n, kc = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv - 1
    with jax.named_scope(MAMBA_SCOPE):
        xz = fault_linear(x, p["in_proj"], ctx)  # (B, S, 2*di)
        xb, z = jnp.split(xz, 2, axis=-1)
        xb = shard_activation(xb, ("batch", "seq", "inner"))

        # the conv sees the carried inputs ahead of this sequence's
        prev = (jnp.zeros((b, kc, di), xb.dtype) if cache is None
                else cache.conv.astype(xb.dtype))
        hist = jnp.concatenate([prev, xb], axis=1)  # (B, kc + S, di)
        xc = jax.nn.silu(_causal_conv(hist, p["conv_w"], p["conv_b"])[:, -s:, :])
        if valid is None:
            tail = hist[:, -kc:, :]
        else:
            # the kc inputs that end at the row's last real token
            ix = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None] + jnp.arange(kc)[None]
            tail = jnp.take_along_axis(hist, ix[..., None], axis=1)
            xc = jnp.where(valid[..., None], xc, 0)

        dbc = fault_linear(xc, p["x_proj"], ctx)  # (B, S, r + 2N)
        r = cfg.resolved_dt_rank
        dt, bmat, cmat = jnp.split(dbc, [r, r + n], axis=-1)
        if cfg.ssm_inner_norm:
            dt = rms_norm(dt, p["dt_norm"], cfg.norm_eps)
            bmat = rms_norm(bmat, p["b_norm"], cfg.norm_eps)
            cmat = rms_norm(cmat, p["c_norm"], cfg.norm_eps)
        dt = jax.nn.softplus(fault_linear(dt, p["dt_w"], ctx) + p["dt_b"])  # (B,S,di)
        if valid is not None:
            dt = jnp.where(valid[..., None], dt, 0)
        a = -jnp.exp(p["a_log"].astype(jnp.float32))  # (di, N)

        with jax.named_scope(SCAN_SCOPE):
            if cache is not None and s == 1:
                y, h_last = selective_step(
                    cache.h, xc[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0], p["d_skip"]
                )
                y = y[:, None]
            else:
                y, h_last = selective_scan(
                    xc, dt, a, bmat, cmat, p["d_skip"], None if cache is None else cache.h
                )

        y = y * jax.nn.silu(z)
        out = fault_linear(y, p["out_proj"], ctx)
    new_cache = None
    if cache is not None or build_cache:
        new_cache = SSMCache(conv=tail.astype(jnp.float32), h=h_last)
    return out, new_cache
