"""DeepSeek-V2-Lite — MLA without query compression, 64 routed experts
top-6 + 2 shared, YaRN RoPE.

[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]
27L, d 2048, 16 heads; layer 0 is a dense SwiGLU FFN (10944); experts
d_ff 1408, the 2 shared experts applied as one SwiGLU of 2 x 1408; softmax
router, top-6 greedy, no renormalisation, routed_scaling_factor 1; MLA
kv_lora 512, q projected directly (q_lora_rank null), qk 128 + 64 rope,
v 128; YaRN factor 40 over 4096 positions, beta 32 / 1, mscale and
mscale_all_dim 0.707; RMSNorm eps 1e-6; untied LM head.
"""
from repro.config import ArchConfig, AttnConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="arXiv:2405.04434",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,      # MLA: all heads share the latent; kept for bookkeeping
    head_dim=128,
    d_ff=10944,         # dense FFN of the first layer
    vocab=102400,
    act="swiglu",
    attn=AttnConfig(mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                                  v_head_dim=128),
                    yarn_factor=40.0, yarn_original_max_position=4096,
                    yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                    yarn_mscale=0.707, yarn_mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408,
                  n_shared_experts=2, d_shared=1408,
                  first_dense_layers=1),
)
