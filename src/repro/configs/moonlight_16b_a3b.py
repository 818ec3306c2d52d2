"""moonlight-16b-a3b [moe]: the DeepSeek-V3 block at 2048 wide: MLA without
a q LoRA, 64 routed experts top-6 (sigmoid, noaux_tc) + 2 shared, one
leading dense layer
[hf:moonshotai/Moonlight-16B-A3B config.json]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="moonlight-16b-a3b", family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264,
    vocab=163840, n_experts=64, n_shared_experts=2, top_k=6, moe_d_ff=1408,
    first_dense_layers=1, router_score="sigmoid", routed_scale=2.446,
    use_mla=True, q_lora_rank=0, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=50000.0, norm="rmsnorm", mlp="swiglu", connection="fal",
    tie_embeddings=False, max_seq=8192,
)
