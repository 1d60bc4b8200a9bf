"""deepseek-v2-lite-16b — MoE with Multi-head Latent Attention [arXiv:2405.04434].

The published config (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json): 27 layers, d_model 2048, 16 heads, vocab 102400, untied
head, rms_norm_eps 1e-6. MLA with kv_lora_rank 512, qk_nope 128, qk_rope
64, v 128 and no q LoRA. Layer 0 is dense (first_k_dense_replace 1,
intermediate_size 10944); the other 26 route each token to 6 of 64
experts of width 1408 by a softmax over the router's logits, without
renormalising the top-6 weights (norm_topk_prob false, routed scaling 1),
beside 2 shared experts merged into one SwiGLU of width 2816. RoPE is
YaRN (factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale and mscale_all_dim 0.707).

Layout departure: the published checkpoints store the rope columns of q
and k interleaved; the program rotates halves. Under the same weights
that is a fixed permutation of those columns, so it changes no result
the program is compared on (its weights are random).
"""
from repro.configs.base import ModelConfig, MLAConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    head_dim=128,
    rope_theta=10000.0,
    rope_scaling=YaRNConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_routed=64, top_k=6, n_shared=2,
                  d_expert=1408, d_shared=2816, norm_topk_prob=False),
    first_k_dense=1,
    act="swiglu",
    tie_embeddings=False,
    source="DeepSeek-V2(-Lite) [arXiv:2405.04434]; "
           "huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json",
)
