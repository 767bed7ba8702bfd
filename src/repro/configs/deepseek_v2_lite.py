"""deepseek-v2-lite [moe, MLA] — hf:deepseek-ai/DeepSeek-V2-Lite.

27L, d_model 2048, 16 heads of latent attention (no q LoRA: a direct
2048 -> 16 x 192 query; kv_lora 512, nope 128 + rope 64, v 128) with YaRN
rope (factor 40 over 4096 original positions), layer 0 dense (d_ff 10944),
layers 1-26 MoE: 64 routed experts of width 1408, top 6 of a greedy
softmax without renormalisation, plus 2 shared experts; vocab 102400,
untied head.
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2_048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope + qk_rope
    d_ff=1_408,
    vocab_size=102_400,
    block_pattern=("moe",),
    rope_theta=10_000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position_embeddings=4_096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    use_mla=True,
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        num_shared_experts=2,
        d_expert=1_408,
        first_k_dense=1,
        d_ff_dense=10_944,
        norm_topk_prob=False,
    ),
)
