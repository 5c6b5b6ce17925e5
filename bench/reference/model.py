"""Plain float32 reference of the served decoders, written for the benchmark.

A Llama/Qwen3-style decoder (RMSNorm, RoPE on rotated halves, optional
per-head q/k RMSNorm, grouped-query causal attention, SwiGLU, tied
embeddings) in straightforward ``jax.numpy``, one whole sequence at a time,
with no cache, paging, packing or batching. It imports nothing of the
program. The weights come from :func:`make_params` here, which the
benchmark also hands to the program.

A chip's faults follow the eFAT paper's weight-stationary mapping: weight
``W[a, b]`` of every GEMM sits on PE ``(a % R, b % C)`` of the (R, C) array,
and a faulty PE contributes zero (fault-aware pruning). The embedding
lookup is not a GEMM and is never masked; the tied unembedding is, in its
(d_model, vocab) view.

Every matmul goes through ``dot``. :func:`f32_dot` is the reference, at
``highest`` precision so that the TPU does not round its operands.
:func:`fp8_dot` is the control: the same operands rounded to float8 e4m3
with one scale per operand, the precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import dims

HIGHEST = jax.lax.Precision.HIGHEST


def f32_dot(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)


def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_dot(spec: str, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


def _fp8_dot_fwd(spec, a, b):
    a8, b8 = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a8, b8, precision=HIGHEST), (a8, b8)


def _fp8_dot_bwd(spec, res, g):
    """The gradient's matmuls at float8 too: the rounded operands and the
    rounded cotangent."""
    _, pull = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST), *res)
    return pull(_fp8(g))


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


# -- weights ------------------------------------------------------------------


def make_params(model: dict, key) -> dict:
    """Random float32 weights in the tree layout the program serves: layer
    weights stacked on a leading layer axis. GEMM weights are normal over
    sqrt(fan_in), the embedding normal x 0.02, and norm scales uniform in
    [0.5, 1.5) so that a dropped scale shows. Jit it to make the weights on
    the device in one call."""
    m = dims(model)
    L, d, hq, hkv, hd, f, V = m["L"], m["d"], m["hq"], m["hkv"], m["hd"], m["f"], m["V"]
    shapes = {
        "wq": (L, d, hq * hd), "wk": (L, d, hkv * hd), "wv": (L, d, hkv * hd),
        "wo": (L, hq * hd, d), "wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d),
    }
    keys = jax.random.split(key, len(shapes) + 6)
    w = {
        name: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
        for k, (name, s) in zip(keys, shapes.items())
    }
    scale = lambda k, s: jax.random.uniform(k, s, jnp.float32, 0.5, 1.5)
    attn = {n: w[n] for n in ("wq", "wk", "wv", "wo")}
    if model.get("qk_norm"):
        attn["q_norm"] = scale(keys[-6], (L, hd))
        attn["k_norm"] = scale(keys[-5], (L, hd))
    return {
        "embed": jax.random.normal(keys[-1], (V, d), jnp.float32) * 0.02,
        "layers": {
            "ln1": {"scale": scale(keys[-4], (L, d))},
            "attn": attn,
            "ln2": {"scale": scale(keys[-3], (L, d))},
            "mlp": {n: w[n] for n in ("wg", "wu", "wd")},
        },
        "final_ln": {"scale": scale(keys[-2], (d,))},
    }


def fault_mask(weight_shape, ok):
    """mask[a, b] = ok[a % R, b % C] over the last two dims of a weight;
    ``ok`` may be traced."""
    d_in, d_out = weight_shape[-2], weight_shape[-1]
    r, c = ok.shape
    return jnp.asarray(ok, jnp.float32)[np.ix_(np.arange(d_in) % r, np.arange(d_out) % c)]


def masked_weights(params: dict, ok) -> dict:
    """The weights the chip computes with: every GEMM weight times its
    fault mask, and the unembedding as the masked transpose of the
    embedding. ``ok`` is the (R, C) healthy-PE grid, or None for a chip
    with no fault."""

    def mask(w):
        return w if ok is None else w * fault_mask(w.shape, ok)

    lay = params["layers"]
    attn = {n: mask(lay["attn"][n]) if n.startswith("w") else lay["attn"][n] for n in lay["attn"]}
    return {
        "embed": params["embed"],
        "unembed": mask(params["embed"].T),
        "layers": {
            "ln1": lay["ln1"]["scale"],
            "ln2": lay["ln2"]["scale"],
            "attn": attn,
            "mlp": {n: mask(v) for n, v in lay["mlp"].items()},
        },
        "final_ln": params["final_ln"]["scale"],
    }


# -- forward --------------------------------------------------------------------


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x: (S, H, D), positions 0 .. S-1; halves rotated as in Llama."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(w: dict, tokens, model: dict, dot=f32_dot, checkpoint: bool = False):
    """Logits (S, V) of one causal sequence ``tokens`` (S,). With
    ``checkpoint`` a gradient keeps only each layer's input and computes
    the rest again, so that a long sequence fits."""
    m = dims(model)
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    eps = float(model["rms_norm_eps"])
    theta = float(model["rope_theta"])
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        a = lw["attn"]
        h = rms_norm(x, lw["ln1"], eps)
        q = dot("sd,de->se", h, a["wq"]).reshape(s, hq, hd)
        k = dot("sd,de->se", h, a["wk"]).reshape(s, hkv, hd)
        v = dot("sd,de->se", h, a["wv"]).reshape(s, hkv, hd)
        if "q_norm" in a:
            q = rms_norm(q, a["q_norm"], eps)
            k = rms_norm(k, a["k_norm"], eps)
        q, k = rope(q, theta), rope(k, theta)
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        scores = dot("qhd,khd->hqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = dot("hqk,khd->qhd", p, v).reshape(s, hq * hd)
        x = x + dot("se,ed->sd", o, a["wo"])
        h = rms_norm(x, lw["ln2"], eps)
        mlp = lw["mlp"]
        g = dot("sd,df->sf", h, mlp["wg"])
        u = dot("sd,df->sf", h, mlp["wu"])
        x = x + dot("sf,fd->sd", jax.nn.silu(g) * u, mlp["wd"])
        return x, None

    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(jax.checkpoint(layer) if checkpoint else layer, x, w["layers"])
    x = rms_norm(x, w["final_ln"], eps)
    return dot("sd,dv->sv", x, w["unembed"])


def token_readings(w: dict, tokens, served, model: dict, dot=f32_dot):
    """Per position of ``tokens`` (S,): the best logit, the logit and the
    log-probability of ``served`` (S,) (the token that position is
    compared at), and the position's own top token. Keeps the (S, V)
    logits inside the call."""
    logits = forward(w, tokens, model, dot)
    at = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    lse = jax.nn.logsumexp(logits, axis=-1)
    return dict(
        best=jnp.max(logits, axis=-1), at=at, logprob=at - lse,
        top=jnp.argmax(logits, axis=-1).astype(jnp.int32),
    )
