"""Plain float32 reference of Jamba (AI21), written for the benchmark.

Jamba's forward pass as its published ``modeling_jamba.py`` computes it,
in straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, one whole sequence at a time, with no kernels, cache,
paging or batching. It imports nothing of the program. The weights come
from :func:`make_params` here, which the benchmark also hands to the
program, so the tree is the program's: the attention layers stacked under
``layers``, the Mamba layers under ``mamba_layers``.

- Layer ``i`` attends iff ``i % attn_layer_period == attn_layer_offset``;
  every other layer is a Mamba-1 mixer. Each layer is pre-norm (RMSNorm)
  and residual, then a pre-norm SwiGLU MLP (``num_experts`` 1: no
  experts), as in ``JambaAttentionDecoderLayer`` / ``JambaMambaDecoderLayer``.
- Attention: grouped-query causal attention with no positional encoding
  (Jamba puts no RoPE on it) and no biases, computed in blocks of query
  positions so that it fits at 16k tokens.
- Mamba mixer (``JambaMambaMixer.slow_forward``): ``in_proj`` to the input
  and gate streams, a depthwise causal conv of width ``mamba_d_conv`` with
  bias, SiLU, ``x_proj`` to dt, B and C, an RMSNorm on each of the three,
  ``dt_proj`` with its bias and a softplus, then the selective scan
  ``h_t = exp(dt_t A) h_{t-1} + dt_t u_t B_t``, ``y_t = <C_t, h_t> + D u_t``
  with ``A = -exp(A_log)``, run one token at a time so that only one
  ``(d_inner, d_state)`` state is held, gated by ``SiLU(gate)`` and
  projected by ``out_proj``. No projection bias (``mamba_proj_bias``
  false).
- The final RMSNorm, and logits from the tied embedding.

Departures from the published model: the weights are random from the
seed (GEMM weights normal over sqrt(fan in), the embedding normal x 0.02,
norm scales and ``D`` uniform in [0.5, 1.5) so that a dropped one shows,
the conv normal over sqrt(width) with a normal x 0.1 bias, ``A_log`` the
published ``log(1 .. d_state)`` and the dt bias the published inverse
softplus of a dt log-uniform in [0.001, 0.1]); the depth is the
configuration's (its ``reduced``); and the published model's ``dtype``
(bfloat16) is float32 here, the point of a reference. The chip is
healthy: no fault mask enters.

Every matmul goes through ``dot``: ``bench/reference/model.py``'s
:func:`f32_dot` for the reference, or its ``fp8_dot`` for the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.model import f32_dot, rms_norm


def dims(model: dict) -> dict:
    """Widths and layer counts from a configuration's Hugging Face keys."""
    d = int(model["hidden_size"])
    hq = int(model["num_attention_heads"])
    L = int(model["num_hidden_layers"])
    period, offset = int(model["attn_layer_period"]), int(model["attn_layer_offset"])
    kinds = ["attn" if i % period == offset else "mamba" for i in range(L)]
    return dict(
        L=L, d=d, hq=hq, hkv=int(model["num_key_value_heads"]), hd=d // hq,
        f=int(model["intermediate_size"]), V=int(model["vocab_size"]),
        di=int(model["mamba_expand"]) * d, n=int(model["mamba_d_state"]),
        k=int(model["mamba_d_conv"]), r=int(model["mamba_dt_rank"]),
        kinds=kinds, La=kinds.count("attn"), Ls=kinds.count("mamba"),
    )


def make_params(model: dict, key) -> dict:
    """Random float32 weights in the tree the program serves (see the
    module's docstring for the law). Jit it to make them on the device."""
    m = dims(model)
    d, hq, hkv, hd, f, V = m["d"], m["hq"], m["hkv"], m["hd"], m["f"], m["V"]
    di, n, k, r, La, Ls = m["di"], m["n"], m["k"], m["r"], m["La"], m["Ls"]
    keys = iter(jax.random.split(key, 32))
    normal = lambda s: jax.random.normal(next(keys), s, jnp.float32)
    gemm = lambda s: normal(s) / math.sqrt(s[-2])
    scale = lambda s: jax.random.uniform(next(keys), s, jnp.float32, 0.5, 1.5)

    def mlp(L):
        return {"wg": gemm((L, d, f)), "wu": gemm((L, d, f)), "wd": gemm((L, f, d))}

    u = jax.random.uniform(next(keys), (Ls, di), jnp.float32)
    dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    ssm = {
        "in_proj": gemm((Ls, d, 2 * di)),
        "conv_w": normal((Ls, k, di)) / math.sqrt(k),
        "conv_b": normal((Ls, di)) * 0.1,
        "x_proj": gemm((Ls, di, r + 2 * n)),
        "dt_w": gemm((Ls, r, di)),
        "dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (Ls, di, n)),
        "d_skip": scale((Ls, di)),
        "out_proj": gemm((Ls, di, d)),
        "dt_norm": scale((Ls, r)),
        "b_norm": scale((Ls, n)),
        "c_norm": scale((Ls, n)),
    }
    return {
        "embed": normal((V, d)) * 0.02,
        "layers": {
            "ln1": {"scale": scale((La, d))},
            "attn": {"wq": gemm((La, d, hq * hd)), "wk": gemm((La, d, hkv * hd)),
                     "wv": gemm((La, d, hkv * hd)), "wo": gemm((La, hq * hd, d))},
            "ln2": {"scale": scale((La, d))},
            "mlp": mlp(La),
        },
        "mamba_layers": {
            "ln1": {"scale": scale((Ls, d))},
            "ssm": ssm,
            "ln2": {"scale": scale((Ls, d))},
            "mlp": mlp(Ls),
        },
        "final_ln": {"scale": scale((d,))},
    }


def _block_size(s: int, most: int = 512) -> int:
    return next(b for b in range(min(most, s), 0, -1) if s % b == 0)


def attention(x, a, m: dict, dot):
    """Causal grouped-query attention over ``x`` (S, d), no positional
    encoding, one block of query positions at a time."""
    s = x.shape[0]
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    q = dot("sd,de->se", x, a["wq"]).reshape(s, hq, hd)
    k = jnp.repeat(dot("sd,de->se", x, a["wk"]).reshape(s, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat(dot("sd,de->se", x, a["wv"]).reshape(s, hkv, hd), hq // hkv, axis=1)
    qb = _block_size(s)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        scores = dot("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        scores = jnp.where((jnp.arange(s)[None] <= rows[:, None])[None], scores, -jnp.inf)
        return dot("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, hq * hd)
    return dot("se,ed->sd", o, a["wo"])


def mamba(x, p, m: dict, eps: float, dot):
    """Jamba's Mamba-1 mixer over ``x`` (S, d), from a zero state."""
    s = x.shape[0]
    di, n, k, r = m["di"], m["n"], m["k"], m["r"]
    xz = dot("sd,de->se", x, p["in_proj"])
    u, gate = xz[:, :di], xz[:, di:]
    # depthwise causal conv: tap j sees the input k - 1 - j steps back
    up = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), u], axis=0)
    conv = sum(p["conv_w"][j] * up[j : j + s] for j in range(k)) + p["conv_b"]
    u = jax.nn.silu(conv)
    dbc = dot("se,ef->sf", u, p["x_proj"])
    dt = rms_norm(dbc[:, :r], p["dt_norm"], eps)
    b = rms_norm(dbc[:, r : r + n], p["b_norm"], eps)
    c = rms_norm(dbc[:, r + n :], p["c_norm"], eps)
    dt = jax.nn.softplus(dot("sr,re->se", dt, p["dt_w"]) + p["dt_b"])
    a = -jnp.exp(p["a_log"])  # (di, n)

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=1)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (u, dt, b, c))
    y = (y + p["d_skip"] * u) * jax.nn.silu(gate)
    return dot("se,ed->sd", y, p["out_proj"])


def hidden(params: dict, tokens, model: dict, dot=f32_dot):
    """The final normed hidden states (S, d) of one causal sequence."""
    m = dims(model)
    eps = float(model["rms_norm_eps"])
    pick = lambda tree, i: jax.tree_util.tree_map(lambda w: w[i], tree)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    seen = {"attn": 0, "mamba": 0}
    for kind in m["kinds"]:
        stack = params["layers" if kind == "attn" else "mamba_layers"]
        lw = pick(stack, seen[kind])
        seen[kind] += 1
        h = rms_norm(x, lw["ln1"]["scale"], eps)
        if kind == "attn":
            x = x + attention(h, lw["attn"], m, dot)
        else:
            x = x + mamba(h, lw["ssm"], m, eps, dot)
        h = rms_norm(x, lw["ln2"]["scale"], eps)
        mlp = lw["mlp"]
        g = dot("sd,df->sf", h, mlp["wg"])
        up = dot("sd,df->sf", h, mlp["wu"])
        x = x + dot("sf,fd->sd", jax.nn.silu(g) * up, mlp["wd"])
    return rms_norm(x, params["final_ln"]["scale"], eps)


def logits(params: dict, tokens, model: dict, dot=f32_dot):
    """Logits (S, V) of one causal sequence (small sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        return dot("sd,vd->sv", hidden(params, tokens, model, dot), params["embed"])


def token_readings(params: dict, tokens, served, model: dict, dot=f32_dot):
    """Per position of ``tokens`` (S,): the best logit, the logit and the
    log-probability of ``served`` (S,), and the position's own top token,
    with the (S, V) logits made one block of positions at a time."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, model, dot)
        s = x.shape[0]
        pb = _block_size(s)

        def block(i):
            xi = jax.lax.dynamic_slice_in_dim(x, i * pb, pb, axis=0)
            lg = dot("sd,vd->sv", xi, params["embed"])
            si = jax.lax.dynamic_slice_in_dim(served, i * pb, pb, axis=0)
            at = jnp.take_along_axis(lg, si[:, None], axis=-1)[:, 0]
            return dict(best=jnp.max(lg, axis=-1), at=at,
                        logprob=at - jax.nn.logsumexp(lg, axis=-1),
                        top=jnp.argmax(lg, axis=-1).astype(jnp.int32))

        out = jax.lax.map(block, jnp.arange(s // pb))
        return jax.tree_util.tree_map(lambda v: v.reshape(s), out)
