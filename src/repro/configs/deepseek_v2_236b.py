"""DeepSeek-V2 236B — MLA + fine-grained MoE [arXiv:2405.04434].

60L d_model=5120 128H d_ff(expert)=1536 vocab=102400; MLA kv_lora_rank=512;
2 shared + 160 routed experts, top-6; first layer dense (d_ff=12288 per the
paper's dense-layer intermediate size). As its published config.json
(huggingface.co/deepseek-ai/DeepSeek-V2): YaRN rotary scaling (factor 40
over 4096 original positions), group-limited routing (the best 3 of 8
groups), unnormalised top-6 weights times 16, an untied output head.
"""

from repro.configs import ArchConfig, YaRN

CONFIG = ArchConfig(
    arch_id="deepseek-v2-236b",
    family="moe",
    source="arXiv:2405.04434",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,  # MLA: per-head K/V are decompressed from the shared latent
    head_dim=128,
    d_ff=12288,  # dense MLP hidden (first_k_dense layers)
    vocab_size=102400,
    attention="mla",
    max_seq_len=163840,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    kv_lora_rank=512,
    q_lora_rank=1536,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=160,
    num_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1536,
    first_k_dense=1,
    router_groups=8,
    router_topk_groups=3,
    norm_topk=False,
    routed_scale=16.0,
    norm_eps=1e-6,
    tie_embeddings=False,
)
