"""Moonlight-16B-A3B (DeepSeek-V3 architecture): 27L d_model=2048 16H with
multi-head latent attention (kv_lora_rank 512, no q-LoRA, qk 128+64, v 128),
layer 0 a dense MLP of 11264, then 26 MoE layers of 64 routed experts
(d_ff 1408, top-6, sigmoid scores with a selection bias, normalised and
scaled by 2.446) and 2 shared experts; vocab 163840, untied; RoPE theta
50000.  [huggingface.co/moonshotai/Moonlight-16B-A3B config.json]"""
from .base import ArchConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab=163840,
    head_dim=128,
    norm="rmsnorm",
    norm_eps=1e-5,
    act="silu",
    rope_kind="rope",
    rope_theta=50_000.0,
    first_dense_layers=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, capacity_factor=None,
                  scoring="sigmoid", routed_scaling=2.446, n_shared_experts=2),
    param_dtype="bfloat16",
)
