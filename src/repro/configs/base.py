"""Configuration dataclasses for the repro framework.

A single ``ModelConfig`` covers every assigned architecture family (dense
GQA, MoE, MLA, SSM, hybrid, encoder-decoder, VLM/audio backbones).  Layer
stacks are described by a repeating ``block_pattern`` of ``BlockKind``
strings; the model builder scans over stacked per-layer parameters so the
traced HLO stays small regardless of depth.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Block kinds
# ---------------------------------------------------------------------------
# "attn"        : global (full-window) self-attention + MLP
# "attn_local"  : sliding-window self-attention + MLP
# "mla"         : multi-head latent attention (DeepSeek-V2) + MLP
# "moe"         : global self-attention + MoE FFN
# "mla_moe"     : MLA attention + MoE FFN
# "mamba"       : Mamba2 SSD block (attention-free)
# "mamba_shared": Mamba2 block followed by a *shared* attention block
#                 (Zamba2: shared params reused at every occurrence)
VALID_BLOCK_KINDS = (
    "attn", "attn_local", "mla", "moe", "mla_moe", "mamba", "mamba_shared",
)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | audio | vlm
    source: str = ""                    # paper / model card citation

    # Core transformer dims
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0                   # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024

    # Layer stacking: the pattern repeats until num_layers blocks are placed.
    block_pattern: Tuple[str, ...] = ("attn",)

    # Attention options
    rope_theta: float = 10000.0
    rope_kind: str = "standard"         # standard | mrope | none
    # YaRN rope scaling (DeepSeek-V2 ``rope_scaling`` type "yarn"); a
    # factor of 0 leaves the rope and the softmax scale unscaled
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    mrope_sections: Tuple[int, ...] = (16, 24, 24)   # t/h/w head-dim split
    sliding_window: int = 4096          # used by attn_local blocks
    logit_softcap: float = 0.0          # gemma2: 50.0 on attention logits
    final_logit_softcap: float = 0.0    # gemma2: 30.0 on lm head
    attn_scale: float = 0.0             # 0 -> 1/sqrt(head_dim)
    qk_norm: bool = False

    # MLA (DeepSeek-V2)
    kv_lora_rank: int = 512
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                   # per-expert hidden (0 -> d_ff)
    first_dense_layers: int = 0         # DeepSeek-V2: layer 0 dense
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True         # renormalise the top-k gate weights
    # the routed experts this device holds: experts [expert_offset,
    # expert_offset + experts_held) of num_experts (0 = all of them); the
    # router keeps all num_experts outputs (expert parallelism's share)
    experts_held: int = 0
    expert_offset: int = 0
    router_aux_coef: float = 0.01

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0                  # 0 -> d_inner // ssm_head_dim
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    ssm_conv_width: int = 4
    shared_attn_every: int = 6          # zamba2: shared attn after every k-th mamba

    # Encoder-decoder (whisper-style)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1500         # stub audio frame count

    # Multimodal stub frontend (vlm / audio)
    num_stub_patches: int = 0           # vlm: patch embeddings prepended

    # Norm / misc
    rmsnorm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    mlp_kind: str = "swiglu"            # swiglu | gelu
    sandwich_norm: bool = False         # gemma2 post-norms
    scale_embeddings: bool = False      # gemma2: embed * sqrt(d_model)
    force_window: int = 0               # >0: every attn layer windowed (long-context variant)

    # Long-context policy
    supports_long_context: bool = False     # may lower long_500k
    long_context_window: int = 4096         # window used by the long variant

    def __post_init__(self):
        for k in self.block_pattern:
            if k not in VALID_BLOCK_KINDS:
                raise ValueError(f"unknown block kind {k!r}")
        if self.expert_offset + self.resolved_experts_held > self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.resolved_experts_held}) outside the "
                f"{self.num_experts} routed experts")

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def resolved_moe_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def resolved_ssm_heads(self) -> int:
        return self.ssm_heads or self.d_inner // self.ssm_head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        """Expand block_pattern to exactly num_layers entries."""
        reps = math.ceil(self.num_layers / len(self.block_pattern))
        return tuple((self.block_pattern * reps)[: self.num_layers])

    def is_attention_free(self) -> bool:
        return all(k in ("mamba",) for k in self.layer_kinds())

    def has_moe(self) -> bool:
        return any(k in ("moe", "mla_moe") for k in self.layer_kinds())

    def has_ssm(self) -> bool:
        return any(k.startswith("mamba") for k in self.layer_kinds())

    # ------------------------------------------------------------------
    def reduced(self, *, num_layers: int = 2, d_model: int = 256,
                num_heads: int = 4, vocab_size: int = 512,
                max_experts: int = 4) -> "ModelConfig":
        """A smoke-test variant of the same family (CPU-runnable)."""
        head_dim = max(32, d_model // num_heads)
        kv = max(1, min(self.num_kv_heads, num_heads))
        # keep the family's pattern but shrink counts
        changes = {
            "num_layers": num_layers,
            "d_model": d_model,
            "num_heads": num_heads,
            "num_kv_heads": kv,
            "head_dim": head_dim,
            "d_ff": d_model * 4,
            "vocab_size": vocab_size,
            "sliding_window": 64,
            "long_context_window": 64,
            "encoder_seq_len":
                32 if self.is_encoder_decoder else self.encoder_seq_len,
            "num_encoder_layers": 2 if self.is_encoder_decoder else 0,
            "num_stub_patches": 8 if self.num_stub_patches else 0,
            "dtype": "float32",
        }
        if self.has_moe():
            changes.update(
                num_experts=min(self.num_experts, max_experts),
                num_experts_per_tok=min(self.num_experts_per_tok, 2),
                num_shared_experts=min(self.num_shared_experts, 1),
                moe_d_ff=d_model * 2,
                first_dense_layers=min(self.first_dense_layers, 1),
                experts_held=0,
                expert_offset=0,
            )
        if self.has_ssm():
            changes.update(
                ssm_state=min(self.ssm_state or 16, 16),
                ssm_head_dim=32,
                ssm_heads=0,
                ssm_chunk=16,
                shared_attn_every=2,
            )
        if self.rope_kind == "mrope":
            t = max(4, (head_dim // 4) // 2 * 2)
            hw = (head_dim - t) // 2
            changes.update(mrope_sections=(t, hw, head_dim - t - hw))
        if self.kv_lora_rank and any(k.startswith("mla") for k in self.layer_kinds()):
            changes.update(kv_lora_rank=64, qk_rope_head_dim=16,
                           qk_nope_head_dim=32, v_head_dim=32)
        return dataclasses.replace(self, **changes)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str                           # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Whether (arch, shape) should lower; returns (ok, reason-if-skip)."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, "full-attention arch: long_500k requires sub-quadratic attention"
    return True, ""


def long_context_variant(cfg: ModelConfig) -> ModelConfig:
    """Sub-quadratic variant for long_500k: every attention layer becomes
    sliding-window (SSM layers untouched)."""
    return dataclasses.replace(cfg, force_window=cfg.long_context_window)
