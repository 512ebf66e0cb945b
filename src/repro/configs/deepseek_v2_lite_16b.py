"""DeepSeek-V2-Lite-16B — MLA (kv_lora=512) + MoE (2 shared + 64 routed,
top-6), first layer dense [arXiv:2405.04434].

Published config: huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json
(YaRN ``rope_scaling``: factor 40, mscale = mscale_all_dim = 0.707,
original length 4096, beta_fast 32, beta_slow 1; softmax gate, greedy
top-6, ``norm_topk_prob`` false, ``routed_scaling_factor`` 1; untied
head).  ``experts_held`` is left at all 64; a deployment that shares each
layer's experts over several chips sets this chip's share."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    source="arXiv:2405.04434",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                   # dense-layer FFN width
    vocab_size=102400,
    block_pattern=("mla_moe",),
    first_dense_layers=1,         # layer 0 is MLA + dense FFN
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1408,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    qk_nope_head_dim=128,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_topk_prob=False,
    tie_embeddings=False,
    rmsnorm_eps=1e-6,
    supports_long_context=False,
)
