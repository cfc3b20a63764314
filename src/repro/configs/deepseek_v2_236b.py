"""DeepSeek-V2 236B — MLA (kv_lora 512), 160 routed experts top-6 + 2 shared.

[arXiv:2405.04434] 60L, d 5120, 128 heads; layer 0 is a dense FFN (12288);
experts d_ff 1536; shared experts 2 x 1536; YaRN RoPE (factor 40 over
4096 positions, mscale 0.707). Its group-limited greedy routing (8 groups,
top 3) and routed_scaling_factor 16 are not modelled: the router is a
plain softmax top-6.
"""
from repro.config import ArchConfig, AttnConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="deepseek-v2-236b",
    family="moe",
    source="arXiv:2405.04434",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,     # MLA: all heads share the latent; kept for bookkeeping
    head_dim=128,
    d_ff=12288,         # dense FFN of the first layer
    vocab=102400,
    act="swiglu",
    attn=AttnConfig(mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536,
                                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                                  v_head_dim=128),
                    yarn_factor=40.0, yarn_original_max_position=4096,
                    yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                    yarn_mscale=0.707, yarn_mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=160, top_k=6, d_expert=1536,
                  n_shared_experts=2, d_shared=1536,
                  first_dense_layers=1),
)
