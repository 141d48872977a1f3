"""deepseek-v3 [moe] — latent attention, group-limited routed experts beside
a shared expert, three leading dense layers, one MTP module.

[hf:deepseek-ai/DeepSeek-V3 config.json; arXiv 2412.19437 §2].  61L,
d_model=7168, 128 heads, MLA q_lora 1536 / kv_lora 512 / qk_nope 128 /
qk_rope 64 / v 128, dense SwiGLU 18432 in layers 0-2, then 256 routed
experts of 2048 (top-8, sigmoid scores with bias correction, 8 groups of
which 4 may be chosen, weights normalised x 2.5) and 1 shared expert of
2048; 1 MTP module; vocab=129280.  Traced only (`LatentMoEConfig`).
"""

from repro.config import LatentMoEConfig

CONFIG = LatentMoEConfig(
    arch_id="deepseek-v3",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    vocab_size=129280,
    d_ff=18432,
    first_dense=3,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=256,
    top_k=8,
    expert_d_ff=2048,
    num_shared_experts=1,
    n_group=8,
    topk_group=4,
    mtp_depth=1,
)
