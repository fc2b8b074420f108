"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; published config.json at
huggingface.co/deepseek-ai/DeepSeek-V2-Lite].

64 routed + 2 shared SwiGLU experts of 1408, top-6 (paper Tab. 1; the
160-routed figure belongs to full V2), MLA with kv_lora 512 and no q
compression, a leading dense layer of 10944. Routing as published:
softmax gates, greedy top-6, ``norm_topk_prob`` false (gates are not
renormalised), ``routed_scaling_factor`` 1, and no token dropped in the
forward pass. Rotary: YaRN, factor 40 over 4096 original positions,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    source="arXiv:2405.04434",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=10944,           # dense FFN used by the first layer
    vocab_size=102_400,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,        # V2-Lite has no q compression
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    num_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1408,
    moe_norm_topk=False,
    moe_capacity_factor=0.0,   # dropless
    first_dense_layers=1,
    rope_theta=10_000.0,
    rope_scaling_factor=40.0,
    rope_original_max_pos=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    norm_eps=1e-6,
    act="swiglu",
)

SMOKE = CONFIG.reduced()
