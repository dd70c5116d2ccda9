"""DeepSeek-V2-Lite — MoE with Multi-head Latent Attention [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite].

27L, d_model 2048, 16 heads, vocab 102400, untied head.  MLA without query
compression (q_lora_rank null): kv_lora_rank 512, qk_nope 128 + qk_rope 64,
v_head 128; YaRN rope (factor 40 over 4096 positions, mscale = mscale_all_dim
= 0.707).  MoE: 2 shared + 64 routed experts of width 1408, softmax scores,
greedy top-6, gates not renormalised; the first layer uses a dense FFN
(width 10944).  Full attention -> long_500k skipped.
"""

from .base import MLAConfig, ModelConfig, MoEConfig, YarnScaling, smoke_variant

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,       # MLA: kv heads = q heads after decompression
    d_ff=1408,
    dense_d_ff=10944,
    first_k_dense=1,
    vocab_size=102400,
    norm_eps=1e-6,
    rope_theta=1e4,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale=0.707, mscale_all_dim=0.707),
    tie_embeddings=False,
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  norm_topk_prob=False),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
)

# the dense layer and two MoE layers; 16 experts, 4 a token, 2 shared
SMOKE = smoke_variant(CONFIG, n_heads=4, n_kv_heads=4, n_layers=3, dense_d_ff=384,
                      moe=MoEConfig(n_experts=16, top_k=4, d_expert=64, n_shared=2,
                                    norm_topk_prob=False))
