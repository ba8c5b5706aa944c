"""moonlight-16b-a3b [hf:moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3; arXiv 2405.04434, 2412.19437]: multi-head latent attention
(kv_lora_rank 512, no q_lora, qk 128 + 64 rotary, v 128), one leading
dense layer (d_ff 11,264), then 26 layers of 64 routed experts of 1,408,
top-6 by sigmoid score plus a selection bias (noaux_tc, one group),
weights normalised and scaled by 2.446, and 2 shared experts."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64, experts_per_token=6, moe_d_ff=1408,
    shared_expert=True, shared_d_ff=2 * 1408, norm_topk=True,
    scoring_func="sigmoid", topk_method="noaux_tc",
    routed_scaling_factor=2.446, first_k_dense_replace=1,
    rope_theta=50_000.0, norm_eps=1e-5,
)
