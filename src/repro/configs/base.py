"""Architecture + shape configuration system.

Every assigned architecture is a frozen ``ArchConfig``; input shapes are
``ShapeConfig``s. The registry maps ``--arch <id>`` strings to configs and
knows which (arch x shape) cells are runnable (sub-quadratic rules etc.).
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass, replace
from typing import Optional

# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell.

    kind: 'train' lowers train_step; 'prefill' lowers prefill; 'decode'
    lowers serve_step (one new token against a KV cache of ``seq_len``).
    """

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Architectures
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    """A complete model architecture description.

    This single dataclass spans all assigned families: dense / moe / ssm /
    hybrid / vlm / audio. Family-specific fields are zero/None when unused.
    """

    name: str
    family: str  # dense | moe | ssm | hybrid | interleaved | vlm | audio
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int

    # --- attention ---
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0  # 0 -> d_model // num_heads
    qk_norm: bool = False
    sliding_window: Optional[int] = None  # SWA window size (tokens)
    rope_theta: float = 10_000.0
    use_rope: bool = True  # False: attention with no positional encoding (Jamba)

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0

    # --- SSM (Mamba-1) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0  # 0 -> ceil(d_model / 16)
    ssm_inner_norm: bool = False  # RMSNorm on the mixer's dt, B and C streams (Jamba)

    # --- interleaved stack (Jamba): layer i is attention iff
    # i % attn_layer_period == attn_layer_offset, every other layer Mamba-1;
    # each layer has its own MLP ---
    attn_layer_period: int = 0
    attn_layer_offset: int = 0

    # --- structure ---
    is_encoder: bool = False  # encoder-only (no causal mask, no decode)
    modality: str = "text"  # text | vision | audio (vision/audio: stub frontend)
    frontend_tokens: int = 0  # stub prefix tokens for vlm (image patches)
    activation: str = "swiglu"  # swiglu | gelu
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # --- fault/accelerator model (paper SIV-A: 256x256 systolic array) ---
    array_rows: int = 256
    array_cols: int = 256

    # --- numerics ---
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    # free-form citation string
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    @property
    def resolved_dt_rank(self) -> int:
        if self.ssm_dt_rank:
            return self.ssm_dt_rank
        return math.ceil(self.d_model / 16)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def has_attention(self) -> bool:
        return self.family in ("dense", "moe", "vlm", "audio", "hybrid", "interleaved")

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid", "interleaved")

    def is_attention_layer(self, i: int) -> bool:
        """Whether layer ``i`` attends: every layer of an attention family,
        one in ``attn_layer_period`` of an interleaved stack, none of an SSM."""
        if self.family == "interleaved":
            return i % self.attn_layer_period == self.attn_layer_offset
        return self.has_attention

    @property
    def num_attn_layers(self) -> int:
        """Layers that hold keys and values: the paged pool's depth."""
        return sum(self.is_attention_layer(i) for i in range(self.num_layers))

    @property
    def num_ssm_layers(self) -> int:
        """Layers that carry recurrent (conv, SSM) state."""
        if self.family == "interleaved":
            return self.num_layers - self.num_attn_layers
        return self.num_layers if self.has_ssm else 0

    @property
    def has_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def sub_quadratic(self) -> bool:
        """True when a 500k-token decode has bounded state (SSM / SWA)."""
        if self.family == "ssm":
            return True
        return self.sliding_window is not None

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (used for roofline 6ND and FSDP policy)."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        hd = self.resolved_head_dim
        attn = ssm = ffn = 0
        if self.has_attention:
            attn = 2 * d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd  # q, o; k, v
        if self.has_ssm:
            di, n, r = self.d_inner, self.ssm_state, self.resolved_dt_rank
            ssm += d * 2 * di  # in_proj (x and z branches)
            ssm += di * self.ssm_conv + di  # depthwise conv and its bias
            ssm += di * (r + 2 * n)  # x_proj -> dt, B, C
            ssm += r * di + di  # dt_proj
            ssm += di * n + di  # A_log, D
            ssm += di * d  # out_proj
            if self.ssm_inner_norm:
                ssm += r + 2 * n  # dt, B and C norms
        if self.has_moe:
            ffn += d * self.num_experts  # router
            ffn += self.num_experts * 3 * d * f  # gate/up/down per expert
        elif f > 0:
            n_mats = 3 if self.activation == "swiglu" else 2
            ffn += n_mats * d * f
        norms = 2 * d
        if self.family == "interleaved":
            n_attn = self.num_attn_layers
            layers = n_attn * attn + (L - n_attn) * ssm + L * (ffn + norms)
        else:
            layers = L * (attn + ssm + ffn + norms)
        emb = v * d
        head = 0 if self.tie_embeddings else v * d
        return layers + emb + head + d  # final norm

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if not self.has_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        inactive = self.num_layers * (self.num_experts - self.experts_per_token) * 3 * d * f
        return self.param_count() - inactive


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCH_MODULES = [
    "falcon_mamba_7b",
    "phi3_mini_3_8b",
    "qwen3_0_6b",
    "llama3_405b",
    "smollm_135m",
    "llama4_maverick_400b_a17b",
    "mixtral_8x22b",
    "internvl2_26b",
    "hubert_xlarge",
    "hymba_1_5b",
    "jamba2_3b",
    "paper_mlp",
]


def _norm(name: str) -> str:
    return name.replace("-", "_").replace(".", "_").lower()


_REGISTRY: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[_norm(cfg.name)] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    _ensure_loaded()
    key = _norm(name)
    if key not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def list_archs(include_paper: bool = False) -> list[str]:
    _ensure_loaded()
    out = sorted(_REGISTRY)
    if not include_paper:
        out = [a for a in out if a != "paper_mlp"]
    return out


def _ensure_loaded() -> None:
    if _REGISTRY:
        return
    for mod in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{mod}")


# ---------------------------------------------------------------------------
# Cell validity (which arch x shape pairs are runnable)
# ---------------------------------------------------------------------------


def cell_skip_reason(cfg: ArchConfig, shape: ShapeConfig) -> Optional[str]:
    """None if runnable, else a human-readable skip reason."""
    if cfg.is_encoder and shape.kind == "decode":
        return "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "full-attention arch: 500k decode needs sub-quadratic attention"
    return None


def valid_cells(arch_names: Optional[list[str]] = None) -> list[tuple[str, str]]:
    _ensure_loaded()
    names = arch_names or list_archs()
    cells = []
    for a in names:
        cfg = get_arch(a)
        for s in SHAPES.values():
            if cell_skip_reason(cfg, s) is None:
                cells.append((a, s.name))
    return cells


# ---------------------------------------------------------------------------
# Reduced configs for CPU smoke tests
# ---------------------------------------------------------------------------


def reduce_config(cfg: ArchConfig) -> ArchConfig:
    """Family-preserving tiny version of ``cfg`` for CPU smoke tests."""
    changes: dict = dict(
        num_layers=2,
        d_model=64,
        vocab_size=97 if cfg.vocab_size else 0,
        norm_eps=cfg.norm_eps,
        array_rows=16,
        array_cols=16,
        dtype="float32",
        param_dtype="float32",
        frontend_tokens=min(cfg.frontend_tokens, 4) if cfg.frontend_tokens else 0,
    )
    if cfg.has_attention and cfg.num_heads:
        ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
        kv = min(2, cfg.num_kv_heads)
        changes.update(
            num_heads=kv * min(ratio, 2),
            num_kv_heads=kv,
            head_dim=16,
        )
    if cfg.d_ff:
        changes["d_ff"] = 128
    if cfg.has_moe:
        changes.update(num_experts=4, experts_per_token=min(cfg.experts_per_token, 2))
    if cfg.has_ssm:
        changes.update(ssm_state=8, ssm_conv=4, ssm_expand=2, ssm_dt_rank=8)
    if cfg.family == "interleaved":
        # one period, attention between two Mamba runs
        changes.update(num_layers=3, attn_layer_period=3, attn_layer_offset=1)
    if cfg.sliding_window:
        changes["sliding_window"] = 32
    return replace(cfg, **changes)


__all__ = [
    "ArchConfig",
    "ShapeConfig",
    "SHAPES",
    "register",
    "get_arch",
    "list_archs",
    "valid_cells",
    "cell_skip_reason",
    "reduce_config",
]
