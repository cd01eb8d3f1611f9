"""moonlight-16b-a3b [moe] — deepseek-v3 block: latent attention (MLA,
no q LoRA), one leading dense layer, then 64 routed experts top-6 with
sigmoid scores, a choice-only correction bias and scale 2.446, plus 2
shared experts [hf:moonshotai/Moonlight-16B-A3B config.json; catalog].

``CONFIG`` is the published model, every expert held. A chip of a
deployment that shares each layer's experts holds a slice of them:
``CONFIG.replace(experts_held=8, expert_offset=8 * chip)``, the router
still 64 wide.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840, first_dense_layers=1,
    num_experts=64, experts_per_token=6, moe_d_ff=1408,
    num_shared_experts=2, router_score="sigmoid", routed_scale=2.446,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=50000.0, norm_eps=1e-5,
)

SMOKE = CONFIG.replace(
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
    vocab_size=256, num_experts=8, experts_per_token=3, moe_d_ff=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, dtype="float32", param_dtype="float32",
)
