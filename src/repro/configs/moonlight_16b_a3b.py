"""moonlight-16b-a3b [moe] — DeepSeek-V3 layers: latent attention (MLA, no
query compression), one leading dense layer, then 64 routed SwiGLU experts
(top-6 by a sigmoid router with a selection bias, weights renormalised and
scaled by 2.446) beside 2 shared experts.
[hf:moonshotai/Moonlight-16B-A3B]
"""
import dataclasses

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,            # qk_nope_head_dim 128 + qk_rope_head_dim 64
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff=1408,               # moe_intermediate_size: each expert's width
    dense_layers=1,          # first_k_dense_replace
    dense_layers_d_ff=11264,  # intermediate_size
    vocab_size=163840,
    rope_base=50000.0,
    act="swiglu",
    norm="rmsnorm",
    # the 2 shared experts run as one always-on SwiGLU of width 2 x 1408
    moe=MoEConfig(n_experts=64, top_k=6, scoring="sigmoid",
                  routed_scale=2.446, dense_residual=True, dense_d_ff=2816),
    source="hf:moonshotai/Moonlight-16B-A3B",
)


def smoke_config() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24,
        kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16, d_ff=32,
        dense_layers_d_ff=96, vocab_size=512,
        moe=MoEConfig(n_experts=4, top_k=2, scoring="sigmoid",
                      routed_scale=2.446, dense_residual=True, dense_d_ff=64))
