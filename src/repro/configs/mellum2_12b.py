"""Mellum2-12B-A2.5B: fine-grained MoE decoder, 3:1 sliding/full attention.

28L d_model=2304 32H (GQA kv=4, head_dim=128) vocab=98304; every layer's
FFN is 64 experts of width 896, top-8 with renormalized softmax gates, no
shared expert.  Three layers of every four attend over a 1024-token
sliding window with default rotary; the fourth attends fully with YaRN
rotary (factor 16 over 8192 original positions).  Both use theta 5e5.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]
"""
from repro.configs import ArchConfig

CONFIG = ArchConfig(
    name="mellum2-12b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_head=128,
    d_ff=896,  # one expert's width (moe_intermediate_size)
    vocab_size=98_304,
    pattern=("attn_local", "attn_local", "attn_local", "attn_full"),
    window=1024,
    n_experts=64,
    top_k=8,
    rope_theta=500_000.0,
    yarn_factor=16.0,
    yarn_original_max_len=8192,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
