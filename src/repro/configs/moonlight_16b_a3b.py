"""moonlight-16b-a3b (Moonlight-16B-A3B) — deepseek-v3 block: latent
attention and a sigmoid-routed MoE with shared experts.

[hf:moonshotai/Moonlight-16B-A3B/blob/main/config.json]  27L d_model=2048,
16 heads. The first layer is dense (SwiGLU, intermediate_size 11264); the
other 26 hold 64 routed experts of width 1408, 6 per token, plus 2 shared
experts. Routing: scoring_func sigmoid, topk_method noaux_tc (a
per-expert correction bias chooses the experts but does not weight
them), norm_topk_prob, routed_scaling_factor 2.446. Attention is MLA:
q_lora_rank null, kv_lora_rank 512, qk_nope_head_dim 128,
qk_rope_head_dim 64, v_head_dim 128. rope_theta 5e4, rms_norm_eps 1e-5,
vocab 163840, untied head. Catalog: MLA, 27L; 64 experts, top-6, 2 shared.
"""
from repro.config import ModelConfig, register


def full() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,              # qk_nope_head_dim
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        v_head_dim=128,
        d_ff=11264,                # the dense first layer
        moe_d_ff=1408,             # per expert
        vocab_size=163840,
        num_experts=64,
        top_k=6,
        num_shared_experts=2,
        first_dense_layers=1,
        router_scoring="sigmoid",
        routed_scaling=2.446,
        rope_theta=5e4,
    )


def reduced() -> ModelConfig:
    return full().replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=16, kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        d_ff=128, moe_d_ff=32, vocab_size=512, num_experts=16,
    )


register("moonlight-16b-a3b", full, reduced)
