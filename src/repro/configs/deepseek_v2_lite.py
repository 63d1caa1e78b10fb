"""deepseek-v2-lite [moe] — latent attention (MLA), 64 routed experts
top-6 with 2 shared experts, one leading dense layer.

27L d_model=2048 16H, MLA: kv_lora_rank=512, no q_lora, qk_nope=128,
qk_rope=64, v_head=128, YaRN rope (factor 40 over 4096); layer 0 dense
d_ff=10944, layers 1-26 MoE: 64 experts of 1408, top-6 softmax gates
not renormalized (routed_scaling_factor 1), 2 shared experts; vocab
102400, untied [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=10944,
    vocab_size=102400,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_k_dense=1,
    norm_topk=False,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling={"type": "yarn", "factor": 40.0,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32.0, "beta_slow": 1.0,
                  "mscale": 0.707, "mscale_all_dim": 0.707},
    norm_eps=1e-6,
)
