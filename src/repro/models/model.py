"""Model assembly: init, forward (train/prefill), decode, loss.

One generic scan-over-layers transformer covering all assigned families:
dense / moe / ssm (mamba) / hybrid (parallel attn+ssm) / interleaved
(Jamba: Mamba layers and attention layers in one stack) / vlm / audio.
Per-layer params are stacked on a leading 'layers' dim and consumed by
``jax.lax.scan`` (compact HLO — one lowered block regardless of depth) with
a configurable remat policy. An interleaved stack keeps one stack per kind
of layer (``layers`` for the attention layers, ``mamba_layers``) and runs
one scan per run of consecutive layers of one kind (:func:`layer_runs`).
Every parameterized GEMM goes through ``fault_linear`` so the chip's
FaultContext masks exactly the weights the systolic mapping places on
faulty PEs.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masking import FaultContext, fault_linear, healthy, mask_selected_params
from repro.launch.sharding import shard_activation
from repro.models.layers import (
    KVCache,
    PagedKVView,
    apply_norm,
    attention_block,
    mlp_block,
    rms_norm,
)
from repro.models.moe import moe_block
from repro.models.ssm import SSMCache, ssm_block

Array = jax.Array

AUDIO_FRAME_DIM = 512  # stub conv-frontend output width (wav2vec2-style)
VISION_PATCH_DIM = 1024  # stub InternViT patch-embedding width


# ---------------------------------------------------------------------------
# Initialization (+ logical-axis specs)
# ---------------------------------------------------------------------------


def _dense(key, shape, in_axis=-2, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


def _attn_specs(cfg):
    s = dict(
        wq=("embed", "qkv"),  # flattened heads*head_dim (unit = head_dim)
        wk=("embed", "kv"),
        wv=("embed", "kv"),
        wo=("qkv", "embed"),
    )
    if cfg.qk_norm:
        s["q_norm"] = (None,)
        s["k_norm"] = (None,)
    return s


def _init_attn(cfg, key):
    hq, hkv, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = dict(
        wq=_dense(ks[0], (d, hq * hd)),
        wk=_dense(ks[1], (d, hkv * hd)),
        wv=_dense(ks[2], (d, hkv * hd)),
        wo=_dense(ks[3], (hq * hd, d)),
    )
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p, _attn_specs(cfg)


def _mlp_specs(cfg):
    if cfg.activation == "swiglu":
        return dict(wg=("embed", "mlp"), wu=("embed", "mlp"), wd=("mlp", "embed"))
    return dict(wi=("embed", "mlp"), wd=("mlp", "embed"))


def _init_mlp(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        p = dict(wg=_dense(ks[0], (d, f)), wu=_dense(ks[1], (d, f)), wd=_dense(ks[2], (f, d)))
    else:
        p = dict(wi=_dense(ks[0], (d, f)), wd=_dense(ks[1], (f, d)))
    return p, _mlp_specs(cfg)


def _moe_specs(cfg):
    return dict(
        router=("embed", None),
        wg=("expert", "embed", "mlp"),
        wu=("expert", "embed", "mlp"),
        wd=("expert", "mlp", "embed"),
    )


def _init_moe(cfg, key):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    p = dict(
        router=_dense(ks[0], (d, e)),
        wg=_dense(ks[1], (e, d, f)),
        wu=_dense(ks[2], (e, d, f)),
        wd=_dense(ks[3], (e, f, d)),
    )
    return p, _moe_specs(cfg)


def _ssm_specs(cfg):
    s = dict(
        in_proj=("embed", "inner"),
        conv_w=(None, "inner"),
        conv_b=("inner",),
        x_proj=("inner", None),
        dt_w=(None, "inner"),
        dt_b=("inner",),
        a_log=("inner", None),
        d_skip=("inner",),
        out_proj=("inner", "embed"),
    )
    if cfg.ssm_inner_norm:
        s.update(dt_norm=(None,), b_norm=(None,), c_norm=(None,))
    return s


def _init_ssm(cfg, key):
    d, di, n, r, k = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.resolved_dt_rank, cfg.ssm_conv
    ks = jax.random.split(key, 6)
    dt_init = jnp.exp(
        jax.random.uniform(ks[4], (di,)) * (math.log(0.1) - math.log(0.001))
        + math.log(0.001)
    )
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # softplus inverse
    p = dict(
        in_proj=_dense(ks[0], (d, 2 * di)),
        conv_w=jax.random.normal(ks[1], (k, di)) * (1.0 / math.sqrt(k)),
        conv_b=jnp.zeros((di,)),
        x_proj=_dense(ks[2], (di, r + 2 * n)),
        dt_w=_dense(ks[3], (r, di)),
        dt_b=dt_bias,
        a_log=jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))),
        d_skip=jnp.ones((di,)),
        out_proj=_dense(ks[5], (di, d)),
    )
    if cfg.ssm_inner_norm:
        p.update(dt_norm=jnp.ones((r,)), b_norm=jnp.ones((n,)), c_norm=jnp.ones((n,)))
    return p, _ssm_specs(cfg)


def _norm_specs(cfg):
    s = dict(scale=(None,))
    if cfg.family == "audio":
        s["bias"] = (None,)
    return s


def _norm_param(cfg):
    p = dict(scale=jnp.ones((cfg.d_model,)))
    if cfg.family == "audio":  # hubert uses LayerNorm
        p["bias"] = jnp.zeros((cfg.d_model,))
    return p, _norm_specs(cfg)


MAMBA_STACK = "mamba_layers"  # params key of an interleaved stack's Mamba layers


def layer_specs(cfg) -> dict:
    """Logical-axes tree of one (unstacked) layer — no allocation. For an
    interleaved stack, one of its attention layers."""
    s: dict = {"ln1": _norm_specs(cfg)}
    if cfg.has_attention:
        s["attn"] = _attn_specs(cfg)
    if cfg.family == "hybrid":
        s["ssm"] = _ssm_specs(cfg)
        s["alpha_attn"] = (None,)
        s["alpha_ssm"] = (None,)
    if cfg.family == "ssm":
        s["ssm"] = _ssm_specs(cfg)
    if cfg.family == "moe":
        s["ln2"] = _norm_specs(cfg)
        s["moe"] = _moe_specs(cfg)
    elif cfg.has_attention:
        s["ln2"] = _norm_specs(cfg)
        s["mlp"] = _mlp_specs(cfg)
    return s


def mamba_layer_specs(cfg) -> dict:
    """Logical-axes tree of one Mamba layer of an interleaved stack."""
    return dict(ln1=_norm_specs(cfg), ssm=_ssm_specs(cfg), ln2=_norm_specs(cfg),
                mlp=_mlp_specs(cfg))


def _init_layer(cfg, key):
    ks = jax.random.split(key, 4)
    p = {}
    p["ln1"], _ = _norm_param(cfg)
    if cfg.has_attention:
        p["attn"], _ = _init_attn(cfg, ks[0])
    if cfg.family == "hybrid":
        p["ssm"], _ = _init_ssm(cfg, ks[1])
        p["alpha_attn"] = jnp.ones((cfg.d_model,))
        p["alpha_ssm"] = jnp.ones((cfg.d_model,))
    if cfg.family == "ssm":
        p["ssm"], _ = _init_ssm(cfg, ks[1])
    if cfg.family == "moe":
        p["ln2"], _ = _norm_param(cfg)
        p["moe"], _ = _init_moe(cfg, ks[2])
    elif cfg.has_attention:
        p["ln2"], _ = _norm_param(cfg)
        p["mlp"], _ = _init_mlp(cfg, ks[2])
    return p, layer_specs(cfg)


def _init_mamba_layer(cfg, key):
    k_ssm, k_mlp = jax.random.split(key)
    return dict(ln1=_norm_param(cfg)[0], ssm=_init_ssm(cfg, k_ssm)[0],
                ln2=_norm_param(cfg)[0], mlp=_init_mlp(cfg, k_mlp)[0])


def param_specs(cfg) -> dict:
    """Logical-axes tree mirroring init_params' structure — no allocation."""
    _is_leaf = lambda a: isinstance(a, tuple) and all(
        x is None or isinstance(x, str) for x in a
    )
    stacked = lambda tree: jax.tree_util.tree_map(
        lambda ax: ("layers",) + ax, tree, is_leaf=_is_leaf
    )
    specs: dict = {"embed": ("vocab", "embed")}
    specs["layers"] = stacked(layer_specs(cfg))
    if cfg.family == "interleaved":
        specs[MAMBA_STACK] = stacked(mamba_layer_specs(cfg))
    specs["final_ln"] = _norm_specs(cfg)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    if cfg.modality in ("audio", "vision"):
        specs["frontend"] = ("frame", "embed")
    return specs


def init_params(cfg, key) -> tuple[dict, dict]:
    """Returns (params, specs): params with [L, ...]-stacked layers, specs a
    mirror pytree of logical-axis tuples ('layers' prepended on stacks)."""
    k_emb, k_layers, k_head, k_front = jax.random.split(key, 4)
    params: dict = {}
    params["embed"] = jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02

    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.family == "interleaved":
        n_attn = cfg.num_attn_layers
        params["layers"] = jax.vmap(lambda k: _init_layer(cfg, k)[0])(layer_keys[:n_attn])
        params[MAMBA_STACK] = jax.vmap(lambda k: _init_mamba_layer(cfg, k))(
            layer_keys[n_attn:])
    else:
        params["layers"] = jax.vmap(lambda k: _init_layer(cfg, k)[0])(layer_keys)

    params["final_ln"], _ = _norm_param(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(k_head, (cfg.d_model, cfg.vocab_size))
    if cfg.modality == "audio":
        params["frontend"] = _dense(k_front, (AUDIO_FRAME_DIM, cfg.d_model))
    elif cfg.modality == "vision":
        params["frontend"] = _dense(k_front, (VISION_PATCH_DIM, cfg.d_model))
    return params, param_specs(cfg)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _block(
    lp: dict,
    x: Array,
    cfg,
    ctx: FaultContext,
    *,
    positions,
    attn_impl: str,
    moe_impl: str,
    moe_cf: float = 1.25,
    cache: Optional[dict] = None,
    build_cache: bool = False,
    cache_len: int = 0,
    segments: Optional[Array] = None,
):
    """One layer. Returns (x, new_cache (dict|None), aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    h = apply_norm(x, lp["ln1"], cfg.norm_eps)

    if cfg.family == "ssm":
        ssm_cache = (
            SSMCache(cache["conv"], cache["h"]) if cache is not None else None
        )
        y, sc = ssm_block(
            lp["ssm"], h, cfg, ctx, cache=ssm_cache, build_cache=build_cache
        )
        if cache is not None:
            new_cache = dict(conv=sc.conv, h=sc.h)
        elif build_cache:
            new_cache = dict(ssm=sc)
        x = x + y
        return x, (new_cache or None), aux

    if cfg.family == "hybrid":
        kv_cache = None
        ssm_cache = None
        if cache is not None:
            kv_cache = KVCache(cache["k"], cache["v"], cache_len)
            ssm_cache = SSMCache(cache["conv"], cache["h"])
        a, kv_out = attention_block(
            lp["attn"], h, cfg, ctx,
            positions=positions, impl=attn_impl, cache=kv_cache,
            return_kv=build_cache,
        )
        sres, sc = ssm_block(
            lp["ssm"], h, cfg, ctx, cache=ssm_cache, build_cache=build_cache
        )
        y = 0.5 * (a * lp["alpha_attn"].astype(a.dtype) + sres * lp["alpha_ssm"].astype(a.dtype))
        x = x + y
        if cache is not None or build_cache:
            if cache is not None:
                new_cache = dict(k=kv_out.k, v=kv_out.v, conv=sc.conv, h=sc.h)
            else:
                new_cache = dict(kv=kv_out, ssm=sc)
        h2 = apply_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp_block(lp["mlp"], h2, cfg, ctx)
        return x, (new_cache or None), aux

    # attention families: dense / moe / vlm / audio
    paged = isinstance(cache, PagedKVView)
    kv_cache = None
    if paged:
        kv_cache = cache
    elif cache is not None:
        kv_cache = KVCache(cache["k"], cache["v"], cache_len)
    a, kv_out = attention_block(
        lp["attn"], h, cfg, ctx,
        positions=positions, impl=attn_impl, cache=kv_cache, return_kv=build_cache,
        segments=segments,
    )
    x = x + a
    if paged:
        new_cache = dict(kp=kv_out.k_pages, vp=kv_out.v_pages)
    elif cache is not None:
        new_cache = dict(k=kv_out.k, v=kv_out.v)
    elif build_cache:
        new_cache = dict(kv=kv_out)
    h2 = apply_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_block(lp["moe"], h2, cfg, ctx, impl=moe_impl, capacity_factor=moe_cf)
    else:
        y = mlp_block(lp["mlp"], h2, cfg, ctx)
    x = x + y
    return x, (new_cache or None), aux


def _mamba_block(
    lp: dict,
    x: Array,
    cfg,
    ctx: FaultContext,
    *,
    state: Optional[dict] = None,
    build_state: bool = False,
    valid: Optional[Array] = None,
    write_mask: Optional[Array] = None,
):
    """One Mamba layer of an interleaved stack: the mixer and an MLP, each
    behind its own pre-norm. ``state`` (``conv``, ``h``) is continued from
    (``models/ssm.py::ssm_block``, as is ``valid``); a slot whose
    ``write_mask`` is False keeps its state. Returns (x, new state dict or
    None, aux)."""
    h = apply_norm(x, lp["ln1"], cfg.norm_eps)
    cache = None if state is None else SSMCache(state["conv"], state["h"])
    y, sc = ssm_block(lp["ssm"], h, cfg, ctx, cache=cache, build_cache=build_state,
                      valid=valid)
    x = x + y
    h2 = apply_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + mlp_block(lp["mlp"], h2, cfg, ctx)
    new = None if sc is None else dict(conv=sc.conv, h=sc.h)
    if new is not None and write_mask is not None:
        keep = lambda n, o: jnp.where(write_mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
        new = jax.tree_util.tree_map(keep, new, state)
    return x, new, jnp.zeros((), jnp.float32)


def layer_runs(cfg) -> list[tuple[str, int, int]]:
    """The layer stack in order, as runs of consecutive layers of one kind:
    (params key of the kind's stack, first layer in that stack, count).
    Every family but the interleaved one is one run of ``layers``."""
    if cfg.family != "interleaved":
        return [("layers", 0, cfg.num_layers)]
    runs: list = []
    seen = {"layers": 0, MAMBA_STACK: 0}
    for i in range(cfg.num_layers):
        key = "layers" if cfg.is_attention_layer(i) else MAMBA_STACK
        if runs and runs[-1][0] == key:
            runs[-1][2] += 1
        else:
            runs.append([key, seen[key], 1])
        seen[key] += 1
    return [tuple(r) for r in runs]


def _run_stack(cfg, params, x, layer, caches=None, wrap=None):
    """Run the layer stack over hidden state ``x``: one ``lax.scan`` per run
    of :func:`layer_runs`. ``layer(mamba, lp, h, lc)`` returns (h, the
    layer's new cache, aux); ``caches`` maps each stack's params key to its
    per-layer caches, stacked like the stack; ``wrap`` (e.g. remat) wraps
    each scan body. Returns (x, summed aux, {stack key: the new caches,
    stacked like the stack})."""
    carry = (x, jnp.zeros((), jnp.float32))
    outs: dict = {}
    for key, start, n in layer_runs(cfg):

        def body(carry, xs, mamba=key == MAMBA_STACK):
            h, aux = carry
            lp, lc = xs
            h, nc, a = layer(mamba, lp, h, lc)
            return (h, aux + a), nc

        xs = (params[key], None if caches is None else caches[key])
        if n != jax.tree_util.tree_leaves(params[key])[0].shape[0]:
            xs = jax.tree_util.tree_map(lambda a: a[start : start + n], xs)
        carry, out = jax.lax.scan(wrap(body) if wrap else body, carry, xs)
        outs.setdefault(key, []).append(out)
    outs = {k: v[0] if len(v) == 1 else jax.tree_util.tree_map(
        lambda *a: jnp.concatenate(a), *v) for k, v in outs.items()}
    return carry[0], carry[1], outs


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_inputs(cfg, params, batch: dict, ctx: FaultContext) -> tuple[Array, Array]:
    """Returns (x (B, S, d) in compute dtype, positions (B, S))."""
    dtype = jnp.dtype(cfg.dtype)
    parts = []
    if cfg.modality == "audio":
        x = fault_linear(batch["embeds"].astype(dtype), params["frontend"], ctx)
        parts.append(x)
    else:
        if cfg.modality == "vision" and "embeds" in batch:
            pv = fault_linear(batch["embeds"].astype(dtype), params["frontend"], ctx)
            parts.append(pv)
        if "tokens" in batch:
            te = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dtype)
            parts.append(te)
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    b, s = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = shard_activation(x, ("batch", "seq_carry", "embed"))
    return x, positions


def unembed(cfg, params, x: Array, ctx: FaultContext) -> Array:
    # a tied model reads ``embed.T`` unless its params carry an ``lm_head``
    # beside it: the serving engine's pre-masked copy (serve/continuous.py)
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = fault_linear(x, w, ctx)
    return shard_activation(logits, ("batch", "seq_carry", "vocab"))


# ---------------------------------------------------------------------------
# Forward (train / eval / prefill-without-cache)
# ---------------------------------------------------------------------------


_REMAT_POLICIES = {
    "none": None,
    "dots": "dots_with_no_batch_dims_saveable",
    "full": "nothing_saveable",
}


def forward(
    params: dict,
    batch: dict,
    cfg,
    ctx: Optional[FaultContext] = None,
    *,
    attn_impl: str = "auto",
    moe_impl: str = "einsum",
    moe_cf: float = 1.25,
    remat: str = "dots",
    fault_apply: str = "per_use",
) -> tuple[Array, Array]:
    """Full-sequence forward. Returns (logits (B, S, V), aux_loss).

    fault_apply: 'per_use' masks inside every matmul (paper-faithful);
    'per_step' pre-masks the array-mapped params once (identical math, one
    weight-sized pass per step instead of per use — see EXPERIMENTS SPerf).
    """
    ctx = ctx or healthy()
    ctx_unembed = ctx
    if fault_apply == "per_step" and ctx.active:
        params = mask_selected_params(params, ctx)
        ctx = healthy()
    x, positions = embed_inputs(cfg, params, batch, ctx)

    def layer(mamba, lp, h, _):
        if mamba:
            h, nc, a = _mamba_block(lp, h, cfg, ctx)
        else:
            h, nc, a = _block(
                lp, h, cfg, ctx,
                positions=positions, attn_impl=attn_impl, moe_impl=moe_impl,
                moe_cf=moe_cf,
            )
        return shard_activation(h, ("batch", "seq_carry", "embed")), nc, a

    wrap = None
    if remat != "none":
        policy = getattr(jax.checkpoint_policies, _REMAT_POLICIES[remat])
        wrap = lambda body: jax.checkpoint(body, policy=policy, prevent_cse=False)

    x, aux, _ = _run_stack(cfg, params, x, layer, wrap=wrap)
    x = apply_norm(x, params["final_ln"], cfg.norm_eps)
    # tied unembed keeps its use-site mask (the lookup needs unmasked rows)
    logits = unembed(cfg, params, x, ctx_unembed if cfg.tie_embeddings else ctx)
    return logits, aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def loss_fn(
    params: dict,
    batch: dict,
    cfg,
    ctx: Optional[FaultContext] = None,
    *,
    attn_impl: str = "auto",
    moe_impl: str = "einsum",
    moe_cf: float = 1.25,
    remat: str = "dots",
    aux_weight: float = 0.01,
    fault_apply: str = "per_use",
) -> tuple[Array, dict]:
    logits, aux = forward(
        params, batch, cfg, ctx, attn_impl=attn_impl, moe_impl=moe_impl,
        moe_cf=moe_cf, remat=remat, fault_apply=fault_apply,
    )
    labels = batch["labels"]
    # frontends may prepend non-text positions (vlm): align to the tail
    if logits.shape[1] != labels.shape[1]:
        logits = logits[:, -labels.shape[1] :]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    logits32 = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = nll.sum() / denom
    acc = (jnp.argmax(logits32, axis=-1) == labels).astype(jnp.float32)
    acc = (acc * mask).sum() / denom
    loss = ce + aux_weight * aux
    return loss, dict(loss=loss, ce=ce, aux=aux, accuracy=acc)


# ---------------------------------------------------------------------------
# KV/SSM cache: init, prefill, decode
# ---------------------------------------------------------------------------


def cache_buffer_len(cfg, seq_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(cfg, batch: int, seq_len: int) -> dict:
    """Zero cache able to hold ``seq_len`` history (window-bounded for SWA).

    Layout: stacked [L, ...] arrays + scalar 'index'."""
    dtype = jnp.dtype(cfg.dtype)
    c: dict = {"index": jnp.zeros((), jnp.int32)}
    if cfg.has_attention:
        L, hkv, hd = cfg.num_attn_layers, cfg.num_kv_heads, cfg.resolved_head_dim
        s_buf = cache_buffer_len(cfg, seq_len)
        c["k"] = jnp.zeros((L, batch, hkv, s_buf, hd), dtype)
        c["v"] = jnp.zeros((L, batch, hkv, s_buf, hd), dtype)
    if cfg.has_ssm:
        c.update(_ssm_state(cfg, batch))
    return c


def _ssm_state(cfg, batch: int) -> dict:
    """Zero recurrent state of every SSM layer for ``batch`` sequences: the
    conv-input tail and the SSM state, float32, stacked over the layers."""
    L, di = cfg.num_ssm_layers, cfg.d_inner
    return dict(conv=jnp.zeros((L, batch, cfg.ssm_conv - 1, di), jnp.float32),
                h=jnp.zeros((L, batch, di, cfg.ssm_state), jnp.float32))


SSM_KEYS = ("conv", "h")


def _stack_caches(cfg, cache: dict) -> dict:
    """A cache dict's per-layer entries as :func:`_run_stack` takes them:
    an interleaved stack's SSM state goes to its Mamba layers, the rest to
    ``layers``."""
    if cfg.family != "interleaved":
        return {"layers": cache}
    return {"layers": {k: v for k, v in cache.items() if k not in SSM_KEYS},
            MAMBA_STACK: {k: cache[k] for k in SSM_KEYS}}


def check_pageable(cfg, what: str) -> None:
    """Refuse a model the paged serving path cannot run: it serves the
    attention families and interleaved stacks, whose SSM state sits per
    slot beside the page chain; an SSM-only or parallel-branch hybrid
    model has no such path yet, and an encoder no decode."""
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"{what} supports attention families and interleaved stacks only, "
            f"not {cfg.family!r}"
        )
    if cfg.is_encoder:
        raise ValueError(f"encoder-only arch has no decode path ({what})")


def cache_specs(cfg) -> dict:
    """Logical axes for the cache pytree (for pjit in/out shardings)."""
    c: dict = {"index": ()}
    if cfg.has_attention:
        c["k"] = ("layers", "batch", "kv_heads", "kv_seq", None)
        c["v"] = ("layers", "batch", "kv_heads", "kv_seq", None)
    if cfg.has_ssm:
        c["conv"] = ("layers", "batch", None, "inner")
        c["h"] = ("layers", "batch", "inner", "state")
    return c


def _ring_perm(s_buf: int, total: int) -> np.ndarray:
    """inv_perm[slot] = index (into the last s_buf tokens) stored at slot."""
    return (np.arange(s_buf) - (total % s_buf)) % s_buf


def prefill(
    params: dict,
    batch: dict,
    cfg,
    ctx: Optional[FaultContext] = None,
    *,
    cache_len: Optional[int] = None,
    attn_impl: str = "auto",
    moe_impl: str = "einsum",
    moe_cf: float = 1.25,
    valid_len=None,
    full_kv: bool = False,
    return_hidden: bool = False,
    segments: Optional[Array] = None,
) -> tuple[Array, dict]:
    """Full-sequence forward that also builds the decode cache.

    Returns (logits_last (B, V), cache). With every new option at its
    default the function is byte-identical to the pre-bucketing prefill.

    ``valid_len`` (traced scalar or ``(B,)``) marks the real prompt length
    of a right-padded batch: logits come from position ``valid_len - 1``
    and the cache assembly keeps the *valid* tokens (the SWA ring
    permutation is computed from ``valid_len``, not the padded width), with
    ``cache["index"] = valid_len`` so decode overwrites the pad garbage.
    Pad columns never contaminate real rows — causality alone excludes
    right-pad keys from every real query.

    ``full_kv`` skips ring/tail truncation and returns the raw
    ``(L, B, Hkv, S, hd)`` KV as the cache's k/v — the paged-admission
    route, where window masking happens at the paged read instead.

    ``return_hidden`` returns the post-norm hidden states ``(B, S, d)`` in
    place of logits so the caller can gather arbitrary positions (packed
    prefill gathers one last-token row per segment) and unembed itself.

    ``segments`` (``(B, S)`` int, with per-segment restarting
    ``batch["positions"]``) packs several prompts into one row; attention
    is masked to same-segment tokens (``repro.models.layers``). An
    interleaved stack's Mamba layers run one scan along the row, so a row
    holds ONE prompt and its pad tail (segment 0), whose steps leave the
    state untouched; the continuous engine never packs two.
    """
    ctx = ctx or healthy()
    x, positions = embed_inputs(cfg, params, batch, ctx)
    b, s = x.shape[0], x.shape[1]
    cache_len = cache_len or s
    s_buf = cache_buffer_len(cfg, cache_len)
    padded = full_kv or segments is not None or valid_len is not None
    if padded and (cfg.family in ("ssm", "hybrid") or cfg.is_encoder):
        # an SSM-only or parallel-branch stack has no pad-aware path, and
        # encoders attend bidirectionally, so pad keys aren't causal-masked
        raise ValueError("padded/packed prefill supports causal attention families only")
    valid = None  # the real tokens, for the Mamba layers' scan
    if segments is not None:
        valid = segments > 0
    elif valid_len is not None:
        vl = jnp.reshape(jnp.asarray(valid_len, jnp.int32), (-1, 1))
        valid = jnp.broadcast_to(jnp.arange(s)[None] < vl, (b, s))

    def layer(mamba, lp, h, _):
        if mamba:
            h, piece, a = _mamba_block(lp, h, cfg, ctx, build_state=True, valid=valid)
        else:
            h, piece, a = _block(
                lp, h, cfg, ctx,
                positions=positions, attn_impl=attn_impl, moe_impl=moe_impl,
                moe_cf=moe_cf, build_cache=True, segments=segments,
            )
        return shard_activation(h, ("batch", "seq_carry", "embed")), piece, a

    x, _aux, stacks = _run_stack(cfg, params, x, layer)
    pieces = stacks["layers"]
    if cfg.family == "interleaved":
        state = stacks[MAMBA_STACK]
    elif cfg.has_ssm:
        state = dict(conv=pieces["ssm"].conv, h=pieces["ssm"].h)
    x = apply_norm(x, params["final_ln"], cfg.norm_eps)
    if return_hidden:
        out = x
    elif valid_len is None:
        out = unembed(cfg, params, x[:, -1:, :], ctx)[:, 0]
    else:
        vl = jnp.asarray(valid_len, jnp.int32)
        if vl.ndim == 0:
            last = jax.lax.dynamic_slice_in_dim(x, vl - 1, 1, axis=1)
        else:
            last = jnp.take_along_axis(x, (vl - 1)[:, None, None], axis=1)
        out = unembed(cfg, params, last, ctx)[:, 0]

    if full_kv:
        k_new, v_new = pieces["kv"]
        dt = jnp.dtype(cfg.dtype)
        index = jnp.asarray(s if valid_len is None else valid_len, jnp.int32)
        kv = dict(k=k_new.astype(dt), v=v_new.astype(dt), index=index)
        return out, (kv if not cfg.has_ssm else {**kv, **state})

    cache = init_cache(cfg, b, cache_len)
    if cfg.has_attention:
        k_new, v_new = pieces["kv"]  # (L, B, Hkv, S, hd)
        if s >= s_buf:
            if valid_len is None:
                tail_k, tail_v = k_new[..., -s_buf:, :], v_new[..., -s_buf:, :]
                perm = jnp.asarray(_ring_perm(s_buf, s)) if cfg.sliding_window and s_buf == cfg.sliding_window else jnp.arange(s_buf)
            else:
                # padded prompt: the last s_buf VALID tokens end at valid_len
                vl = jnp.asarray(valid_len, jnp.int32)
                start = jnp.clip(vl - s_buf, 0, s - s_buf)
                tail_k = jax.lax.dynamic_slice_in_dim(k_new, start, s_buf, axis=3)
                tail_v = jax.lax.dynamic_slice_in_dim(v_new, start, s_buf, axis=3)
                if cfg.sliding_window and s_buf == cfg.sliding_window:
                    # generalizes _ring_perm to a traced total: before the
                    # ring wraps (vl < s_buf) the layout is linear
                    shift = jnp.where(vl >= s_buf, vl % s_buf, 0)
                    perm = (jnp.arange(s_buf) - shift) % s_buf
                else:
                    perm = jnp.arange(s_buf)
            cache["k"] = jnp.take(tail_k, perm, axis=3).astype(cache["k"].dtype)
            cache["v"] = jnp.take(tail_v, perm, axis=3).astype(cache["v"].dtype)
        else:
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), 0, axis=3
            )
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), 0, axis=3
            )
    if cfg.has_ssm:
        cache.update(state)
    cache["index"] = jnp.asarray(s if valid_len is None else valid_len, jnp.int32)
    return out, cache


def prefill_chunk(
    params: dict,
    tokens: Array,  # (1, C) — one chunk of one request's prompt
    cfg,
    ctx: Optional[FaultContext] = None,
    *,
    k_pages: Array,  # (L, P, Hkv, page, hd) shared pool
    v_pages: Array,
    row: Array,  # (max_pages_per_seq,) int32 — this slot's page chain
    prefix_len,  # traced scalar: tokens already prefilled (multiple of C)
    valid_len,  # traced scalar: real tokens in this chunk (== C except last)
    ssm_state: Optional[dict] = None,  # interleaved: (conv, h) after the prefix
    moe_impl: str = "einsum",
    moe_cf: float = 1.25,
) -> tuple[Array, Array, Array, Optional[dict]]:
    """One chunked-prefill step: continue a prompt against its paged prefix.

    Gathers the slot's page chain into a dense buffer, runs the chunk as a
    multi-token continuation (causal attention at ``q_offset=prefix_len``
    over ``prefix + chunk`` valid keys — sliding windows are handled by the
    dense window mask, never the ring buffer, so chunk boundaries crossing
    the window are exact), and returns
    ``(logits (1, V) at valid_len - 1, k_chunk, v_chunk (L, 1, Hkv, C, hd),
    ssm_state)`` for the caller to scatter into the pool. An interleaved
    stack's Mamba layers continue from ``ssm_state`` (``conv``
    ``(Ls, 1, K-1, d_inner)``, ``h`` ``(Ls, 1, d_inner, N)``) and return the
    state after the chunk's last real token (the pad tail leaves it as it
    was); other models return None there. ONE compiled shape covers every
    chunk of every prompt: prefix/valid are traced, the chain width is the
    engine-wide ``max_pages_per_seq``.
    """
    ctx = ctx or healthy()
    check_pageable(cfg, "chunked prefill")
    b, s = tokens.shape
    if b != 1:
        raise ValueError(f"chunked prefill is one request per dispatch, got batch {b}")
    L, _, hkv, page, hd = k_pages.shape
    cap = row.shape[0] * page
    # buffer must fit any chunk write at a chunk-aligned prefix, and must
    # dodge the ring-buffer branch in attention_block (its causal=False
    # shortcut is decode-only — wrong for multi-token chunks)
    w_buf = -(-cap // s) * s
    if cfg.sliding_window and w_buf == cfg.sliding_window:
        w_buf += page
    prefix = jnp.asarray(prefix_len, jnp.int32)
    vl = jnp.asarray(valid_len, jnp.int32)
    positions = jnp.broadcast_to(prefix + jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = shard_activation(x, ("batch", "seq", "embed"))

    def chain_dense(pool):  # (L, P, Hkv, page, hd) -> (L, 1, Hkv, w_buf, hd)
        g = jnp.transpose(jnp.take(pool, row, axis=1), (0, 2, 1, 3, 4))
        g = g.reshape(L, hkv, cap, hd)
        return jnp.pad(g, ((0, 0), (0, 0), (0, w_buf - cap), (0, 0)))[:, None]

    caches = {"layers": {"k": chain_dense(k_pages), "v": chain_dense(v_pages)}}
    if cfg.family == "interleaved":
        caches[MAMBA_STACK] = ssm_state
    valid = jnp.arange(s)[None] < vl

    def layer(mamba, lp, h, lc):
        if mamba:
            return _mamba_block(lp, h, cfg, ctx, state=lc, valid=valid)
        return _block(
            lp, h, cfg, ctx,
            positions=positions, attn_impl="dense", moe_impl=moe_impl,
            moe_cf=moe_cf, cache=lc, cache_len=prefix,
        )

    x, _aux, new = _run_stack(cfg, params, x, layer, caches)
    x = apply_norm(x, params["final_ln"], cfg.norm_eps)
    last = jax.lax.dynamic_slice_in_dim(x, vl - 1, 1, axis=1)
    logits = unembed(cfg, params, last, ctx)[:, 0]
    k_chunk = jax.lax.dynamic_slice_in_dim(new["layers"]["k"], prefix, s, axis=3)
    v_chunk = jax.lax.dynamic_slice_in_dim(new["layers"]["v"], prefix, s, axis=3)
    return logits, k_chunk, v_chunk, new.get(MAMBA_STACK)


def decode_step(
    params: dict,
    tokens: Array,  # (B, s_new) — usually s_new == 1
    cache: dict,
    cfg,
    ctx: Optional[FaultContext] = None,
    *,
    moe_impl: str = "einsum",
    moe_cf: float = 1.25,
    active: Optional[Array] = None,
) -> tuple[Array, dict]:
    """One autoregressive step against the cache. Returns (logits, cache').

    ``cache`` is either the dense cache from :func:`prefill`/:func:`init_cache`
    or a paged cache (``repro.serve.kvcache.init_paged_cache``), detected by
    its ``k_pages`` key. The paged path reads each slot's page chain with a
    gather and supports per-slot positions — slot ``b`` sits at its own
    ``seq_lens[b]`` — plus ``active`` masking: inactive slots neither write
    KV (their token lands on the reserved scratch page) nor advance their
    length. ``active`` is ignored on the dense path, whose single scalar
    index always advances.
    """
    ctx = ctx or healthy()
    if "k_pages" in cache:
        return _decode_step_paged(
            params, tokens, cache, cfg, ctx,
            moe_impl=moe_impl, moe_cf=moe_cf, active=active,
        )
    b, s = tokens.shape
    index = cache["index"]
    positions = index + jnp.arange(s, dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(positions, (b, s))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = shard_activation(x, ("batch", "seq", "embed"))

    layer_cache = {k: v for k, v in cache.items() if k != "index"}

    def layer(mamba, lp, h, lc):
        if mamba:
            return _mamba_block(lp, h, cfg, ctx, state=lc)
        return _block(
            lp, h, cfg, ctx,
            positions=positions, attn_impl="dense", moe_impl=moe_impl,
            moe_cf=moe_cf, cache=lc, cache_len=index,
        )

    x, _aux, new = _run_stack(cfg, params, x, layer, _stack_caches(cfg, layer_cache))
    x = apply_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed(cfg, params, x, ctx)
    new_cache = {k: v for stack in new.values() for k, v in stack.items()}
    new_cache["index"] = index + s
    return logits, new_cache


def init_paged_cache(
    cfg, num_pages: int, page_size: int, num_slots: int, max_pages_per_seq: int
) -> dict:
    """Zero paged KV cache: a shared page pool + per-slot block tables.

    Layout: ``k_pages``/``v_pages`` are ``(L, num_pages, Hkv, page_size, hd)``
    pools (page 0 reserved as the scratch page — see
    ``repro.serve.kvcache.PageAllocator``), ``block_tables`` is
    ``(num_slots, max_pages_per_seq)`` int32 page ids and ``seq_lens`` is the
    per-slot cached-token count. The pool covers the attention layers; an
    interleaved stack's Mamba layers keep their state per slot beside it
    (``conv``, ``h``: O(1) in sequence length, so nothing to page).
    """
    check_pageable(cfg, "paged KV cache")
    dtype = jnp.dtype(cfg.dtype)
    L, hkv, hd = cfg.num_attn_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    cache = {
        "k_pages": jnp.zeros((L, num_pages, hkv, page_size, hd), dtype),
        "v_pages": jnp.zeros((L, num_pages, hkv, page_size, hd), dtype),
        "block_tables": jnp.zeros((num_slots, max_pages_per_seq), jnp.int32),
        "seq_lens": jnp.zeros((num_slots,), jnp.int32),
    }
    if cfg.has_ssm:
        cache.update(_ssm_state(cfg, num_slots))
    return cache


def _decode_step_paged(
    params: dict,
    tokens: Array,  # (S, 1) — one token per slot
    cache: dict,
    cfg,
    ctx: FaultContext,
    *,
    moe_impl: str = "einsum",
    moe_cf: float = 1.25,
    active: Optional[Array] = None,
) -> tuple[Array, dict]:
    """Gather-based paged decode: per-slot positions, shared page pool; an
    interleaved stack's Mamba layers step each slot's own state (a slot
    that is not ``active`` keeps it)."""
    check_pageable(cfg, "paged decode")
    b, s = tokens.shape
    lens = cache["seq_lens"]
    bt = cache["block_tables"]
    positions = jnp.broadcast_to(lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = shard_activation(x, ("batch", "seq", "embed"))

    def layer(mamba, lp, h, lc):
        if mamba:
            return _mamba_block(lp, h, cfg, ctx, state=lc, write_mask=active)
        kp, vp = lc
        view = PagedKVView(kp, vp, bt, lens, active)
        return _block(
            lp, h, cfg, ctx,
            positions=positions, attn_impl="dense", moe_impl=moe_impl,
            moe_cf=moe_cf, cache=view,
        )

    caches = {"layers": (cache["k_pages"], cache["v_pages"])}
    if cfg.family == "interleaved":
        caches[MAMBA_STACK] = {k: cache[k] for k in SSM_KEYS}
    x, _aux, new = _run_stack(cfg, params, x, layer, caches)
    x = apply_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed(cfg, params, x, ctx)
    advanced = lens + s if active is None else jnp.where(active, lens + s, lens)
    return logits, dict(
        k_pages=new["layers"]["kp"],
        v_pages=new["layers"]["vp"],
        block_tables=bt,
        seq_lens=advanced,
        **new.get(MAMBA_STACK, {}),
    )
