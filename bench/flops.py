"""Operation and byte counts from a configuration's shapes.

The benchmark's own arithmetic for utilization and roofline shares. Model
FLOPs count the algorithm's work only: the GEMMs of every layer, attention
over the keys a token sees, and the unembedding where logits are produced.
Fault masks, recomputation and padding are not counted.
"""
from __future__ import annotations


def dims(model: dict) -> dict:
    """Model widths from a configuration file's ``model`` block (Hugging
    Face key names)."""
    d = int(model["hidden_size"])
    hq = int(model["num_attention_heads"])
    return dict(
        L=int(model["num_hidden_layers"]),
        d=d,
        hq=hq,
        hkv=int(model["num_key_value_heads"]),
        hd=int(model.get("head_dim") or d // hq),
        f=int(model["intermediate_size"]),
        V=int(model["vocab_size"]),
    )


def layer_gemm_params(model: dict) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o and
    the SwiGLU gate, up and down projections)."""
    m = dims(model)
    d, hq, hkv, hd, f = m["d"], m["hq"], m["hkv"], m["hd"], m["f"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def token_flops(model: dict, keys: int, logits: bool = True) -> float:
    """Forward FLOPs of one token that attends over ``keys`` keys."""
    m = dims(model)
    attn = 4.0 * m["hq"] * m["hd"] * keys  # q.k and p.v per key
    out = 2.0 * m["L"] * layer_gemm_params(model) + m["L"] * attn
    if logits:
        out += 2.0 * m["d"] * m["V"]
    return out


def prompt_flops(model: dict, start: int, n: int, logits: bool = True) -> float:
    """Forward FLOPs of prompt positions ``start .. start + n - 1`` under
    causal attention, with logits for the last position only."""
    if n <= 0:
        return 0.0
    m = dims(model)
    keys = n * start + n * (n + 1) / 2.0  # sum of (j + 1) over the positions
    out = 2.0 * m["L"] * layer_gemm_params(model) * n
    out += 4.0 * m["L"] * m["hq"] * m["hd"] * keys
    if logits:
        out += 2.0 * m["d"] * m["V"]
    return out


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token of a causal sequence of
    ``seq_len``: three times the forward, logits at every position."""
    m = dims(model)
    mean_keys = (seq_len + 1) / 2.0
    fwd = 2.0 * m["L"] * layer_gemm_params(model)
    fwd += 4.0 * m["L"] * m["hq"] * m["hd"] * mean_keys
    fwd += 2.0 * m["d"] * m["V"]
    return 3.0 * fwd


def masked_matmul_flops_bytes(m: int, k: int, n: int, r: int, c: int, *,
                              x_bytes: int, w_bytes: int, out_bytes: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one masked GEMM ``(m, k) @ mask(k, n)`` at its
    logical (unpadded) shape: the GEMM plus the mask multiply, one HBM
    touch per operand and the float32 (r, c) mask."""
    flops = 2.0 * m * k * n + k * n
    byts = m * k * x_bytes + k * n * w_bytes + m * n * out_bytes + r * c * 4
    return flops, float(byts)
