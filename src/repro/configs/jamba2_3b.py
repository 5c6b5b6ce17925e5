"""jamba2-3b — Mamba-1 layers interleaved with attention (AI21 Jamba).

28L d_model=2560 20H (kv=1) head_dim=128 d_ff=8192 vocab=65536, tied.
Layer i attends iff i % 14 == 7 (2 of 28); the other 26 are Mamba-1 mixers
(d_state 16, d_conv 4 with bias, expand 2, dt_rank 160) with RMSNorms on
their dt, B and C streams. Every layer has a SwiGLU MLP (num_experts 1).
The attention layers carry no positional encoding.
[hf:ai21labs/AI21-Jamba2-3B config.json]
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(
    ArchConfig(
        name="jamba2-3b",
        family="interleaved",
        num_layers=28,
        d_model=2560,
        num_heads=20,
        num_kv_heads=1,
        head_dim=128,
        d_ff=8192,
        vocab_size=65536,
        use_rope=False,
        ssm_state=16,
        ssm_conv=4,
        ssm_expand=2,
        ssm_dt_rank=160,
        ssm_inner_norm=True,
        attn_layer_period=14,
        attn_layer_offset=7,
        norm_eps=1e-6,
        tie_embeddings=True,
        activation="swiglu",
        source="hf:ai21labs/AI21-Jamba2-3B",
    )
)
