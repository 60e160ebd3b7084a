"""moonlight-16b-a3b — DeepSeek-V3 block: MLA, sigmoid top-6 of 64 experts.

[hf:moonshotai/Moonlight-16B-A3B] 27L d_model=2048 16H; MLA with no q
LoRA (kv_lora_rank 512, q/k 128 + 64 rotary, v 128); layer 0 a dense
SwiGLU of 11264, layers 1-26 MoE: 64 routed experts of 1408, top-6 by
sigmoid score plus a selection bias (noaux_tc, n_group = topk_group = 1),
gates normalised and scaled by 2.446, 2 shared experts; rms eps 1e-5,
rope theta 5e4, vocab 163840 untied.
"""
from repro.configs.base import MLA, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,                  # qk_nope_head_dim
    qk_rope_head_dim=64,
    v_head_dim=128,
    kv_lora_rank=512,
    d_ff=11_264,                   # the leading dense layer
    first_k_dense=1,
    vocab_size=163_840,
    block_pattern=(MLA,),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, shared_experts=2,
                  scoring="sigmoid", routed_scale=2.446, dropless=True),
    mlp_kind="swiglu",
    norm_eps=1e-5,
    rope_theta=50_000.0,
    tie_embeddings=False,
)
