"""Model / run configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig`.  The
config is a plain frozen dataclass so it can be hashed into jit static
arguments and printed into EXPERIMENTS.md verbatim.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds understood by models/registry.py
# ---------------------------------------------------------------------------
DENSE = "dense"          # attention + MLP decoder block
MOE = "moe"              # attention + routed-expert block
MLSTM = "mlstm"          # xLSTM matrix-memory block
SLSTM = "slstm"          # xLSTM scalar-memory block (sequential)
MAMBA2 = "mamba2"        # SSD block
SHARED_ATTN = "shared_attn"  # Zamba2 shared transformer block


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int               # the router's width: every routed expert
    top_k: int
    expert_d_ff: int
    capacity_factor: float = 1.25  # training's capacity dispatch only
    router_aux_coef: float = 0.01  # load-balance loss weight
    n_shared_experts: int = 0      # experts every token passes through
    norm_topk_prob: bool = True    # renormalise the top-k probabilities
    # [first, first + count) of the routed experts this device holds (expert
    # parallelism); None holds all.  Routing still runs over num_experts.
    held: Optional[Tuple[int, int]] = None
    first_dense_layers: int = 0    # leading layers with a dense MLP of d_ff

    @property
    def held_range(self) -> Tuple[int, int]:
        """(first held expert, number held)."""
        return self.held if self.held is not None else (0, self.num_experts)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434): keys
    and values are expanded from a cached latent ``c_kv`` of
    ``kv_lora_rank`` values, beside one rotary key of ``qk_rope_head_dim``
    shared by every head.  Queries are not compressed (no q-LoRA)."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv:2309.00071), with DeepSeek-V2's names."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Parameters shared by mLSTM / Mamba2 style blocks."""
    state_size: int = 64          # N (mamba2 state dim per head)
    conv_width: int = 4           # depthwise conv width (mamba2)
    expand: int = 2               # inner expansion factor
    chunk_size: int = 256         # chunked-scan block length
    num_ssm_heads: int = 0        # 0 -> derived from d_inner/headdim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    # --- attention options -------------------------------------------------
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    sliding_window: int = 0        # 0 -> full attention
    local_global_pattern: int = 0  # k -> k local layers per 1 global layer
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    use_rope: bool = True          # False -> absolute (sinusoidal) positions
    rope_scaling: Optional[YarnScaling] = None
    mla: Optional[MLAConfig] = None    # latent attention in place of MHA/GQA
    norm_type: str = "rms"         # "rms" | "layernorm"
    mlp_kind: str = "gated_silu"   # "gated_silu" | "gelu"
    # --- mixture of experts -------------------------------------------------
    moe: Optional[MoEConfig] = None
    # --- ssm / hybrid -------------------------------------------------------
    ssm: Optional[SSMConfig] = None
    hybrid_attn_period: int = 0    # zamba2: shared attn block every k blocks
    # --- enc-dec (audio) ----------------------------------------------------
    encoder_layers: int = 0        # 0 -> decoder-only
    encoder_seq: int = 0           # fixed encoder sequence (e.g. 1500 frames)
    # --- vlm ----------------------------------------------------------------
    vision_tokens: int = 0         # prefix patch-embedding count (stub frontend)
    # --- early exit (the paper's technique) ---------------------------------
    exit_layers: Tuple[int, ...] = ()   # 1-based layer indices with exit heads
    # --- citation -----------------------------------------------------------
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_recurrent(self) -> bool:
        """True when decode state is O(1) (no growing KV for ssm blocks)."""
        return self.arch_type == "ssm"

    @property
    def supports_long_decode(self) -> bool:
        """Sub-quadratic decode: ssm, hybrid, or sliding-window dense."""
        return self.arch_type in ("ssm", "hybrid") or (
            self.sliding_window > 0 and self.local_global_pattern > 0
        )

    @property
    def has_decode(self) -> bool:
        """All assigned archs have a decoder (whisper is enc-dec)."""
        return True

    def block_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind sequence for the *decoder* stack."""
        if self.arch_type == "moe":
            k = self.moe.first_dense_layers if self.moe else 0
            return (DENSE,) * k + (MOE,) * (self.n_layers - k)
        if self.arch_type == "ssm":
            # xLSTM: sLSTM block at every 7th position per arXiv:2405.04517
            # ([1:7] sLSTM:mLSTM ratio for the 350M-class model family);
            # remaining blocks mLSTM.
            kinds = []
            for i in range(self.n_layers):
                kinds.append(SLSTM if (i % 7 == 3) else MLSTM)
            return tuple(kinds)
        if self.arch_type == "hybrid":
            # Zamba2: mamba2 backbone, shared attention block applied every
            # `hybrid_attn_period` layers.
            period = self.hybrid_attn_period or 6
            kinds = []
            for i in range(self.n_layers):
                kinds.append(SHARED_ATTN if (i % period == period - 1) else MAMBA2)
            return tuple(kinds)
        return (DENSE,) * self.n_layers

    def layer_windows(self) -> Tuple[int, ...]:
        """Per-decoder-layer sliding window (0 = full attention)."""
        if self.sliding_window and self.local_global_pattern:
            period = self.local_global_pattern + 1
            return tuple(self.sliding_window if (i % period) < self.local_global_pattern
                         else 0 for i in range(self.n_layers))
        if self.sliding_window:
            return (self.sliding_window,) * self.n_layers
        return (0,) * self.n_layers

    def validate(self) -> "ModelConfig":
        assert self.n_heads % max(self.n_kv_heads, 1) == 0, self.name
        if self.exit_layers:
            assert all(1 <= l <= self.n_layers for l in self.exit_layers)
            assert tuple(sorted(self.exit_layers)) == self.exit_layers
        if self.arch_type == "moe":
            assert self.moe is not None
            first, count = self.moe.held_range
            assert 0 <= first and 0 < count and first + count <= \
                self.moe.num_experts, self.name
        return self


def reduced(cfg: ModelConfig, *, n_layers: int = 2, d_model: int = 256,
            n_heads: int = 4, vocab: int = 512) -> ModelConfig:
    """A smoke-test-sized variant of the same family (assignment rule:
    ≤2 layers, d_model ≤ 512, ≤4 experts)."""
    kv = min(cfg.n_kv_heads, n_heads)
    while n_heads % kv:
        kv -= 1
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, num_experts=4, held=None,
                                  top_k=min(2, cfg.moe.top_k),
                                  expert_d_ff=max(64, d_model // 4))
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, state_size=16, chunk_size=32)
    exits = (1,) if n_layers >= 2 else ()
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=kv,
        head_dim=d_model // n_heads,
        d_ff=0 if cfg.d_ff == 0 else d_model * 2,
        vocab_size=vocab,
        moe=moe,
        ssm=ssm,
        encoder_layers=min(cfg.encoder_layers, n_layers),
        encoder_seq=min(cfg.encoder_seq, 64),
        vision_tokens=min(cfg.vision_tokens, 16),
        sliding_window=min(cfg.sliding_window, 32) if cfg.sliding_window else 0,
        hybrid_attn_period=2 if cfg.hybrid_attn_period else 0,
        exit_layers=exits,
    ).validate()
