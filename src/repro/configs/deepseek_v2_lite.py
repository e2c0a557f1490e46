"""deepseek-v2-lite — MoE 27L d_model=2048 16H, multi-head latent attention
(kv latent 512, rope key 64, nope 128, v 128, no q-LoRA, YaRN x40), layer 0
a dense SwiGLU of 10944, then 64 routed experts of 1408 (top-6, softmax,
not renormalized) and 2 shared experts, vocab=102400.  15.7B parameters.

[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]
"""
from repro.configs.base import MOE, ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family=MOE,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,                # per-expert ffn dim
    vocab_size=102400,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                     beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                     mscale_all_dim=0.707),
    num_experts=64,
    experts_per_token=6,
    n_routed_experts=64,      # all held; a deployment's share holds fewer
    num_shared_experts=2,
    first_k_dense=1,
    dense_d_ff=10944,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    norm_eps=1e-6,
    max_seq_len=163840,
)
