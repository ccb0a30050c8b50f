"""Moonlight-16B-A3B: DeepSeek-V3 blocks, multi-head latent attention and
64 fine-grained routed experts (top-6, sigmoid routing) plus 2 shared
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]."""
from repro.configs.base import MLA, ModelConfig, MoEConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11_264,                 # the leading dense layer's FFN
    vocab=163_840,
    head_dim=192,                # qk_nope_head_dim + qk_rope_head_dim
    block_pattern=(MLA,),
    rope_theta=50_000.0,
    norm_eps=1e-5,
    kv_lora_rank=512,            # no query compression (q_lora_rank null)
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_dense_layers=1,
    moe=MoEConfig(n_experts=64, top_k=6, shared_expert=True,
                  d_expert=1408, d_shared=2 * 1408, scoring="sigmoid",
                  routed_scale=2.446),
    experts_held=64,             # all, on one host; a chip's share is fewer
    source="hf:moonshotai/Moonlight-16B-A3B; hf",
))
