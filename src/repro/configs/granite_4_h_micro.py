"""Granite 4.0-H Micro (ibm-granite/granite-4.0-h-micro, model_type
``granitemoehybrid``, no experts): 40 layers, a period of 10 with Mamba-2
mixers at 9 places and one NoPE GQA attention mixer (32 query and 8
key-value heads of 64) at place 5; every mixer is followed by its own
SwiGLU MLP. Scalar multipliers: embedding x12, each branch x0.22 before its
residual add, attention softmax scale 1/64, logits /8. Mamba-2: 64 heads x
64, d_state 128, one B/C group, expand 2, conv 4 with bias."""
import dataclasses

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,  # hidden_size / num_attention_heads, as the model derives it
    d_ff=8192,
    vocab_size=100352,
    pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    norm_kind="rmsnorm",
    use_rope=False,
    attn_scale=0.015625,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    mixer_mlp=True,
    ssm_state=128,
    ssm_head_dim=64,
    mamba_conv_bias=True,
    mamba_chunk=256,
    tie_embeddings=True,
    long_context_ok=True,  # 36 constant-size states, 4 growing KV caches
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)


def smoke() -> ArchConfig:
    return dataclasses.replace(
        CONFIG,
        num_layers=2,
        d_model=256,
        num_heads=8,
        num_kv_heads=2,
        head_dim=32,
        d_ff=512,
        vocab_size=512,
        pattern=("mamba", "attn"),
        ssm_state=16,
        ssm_head_dim=32,
        num_tasks=4,
        mamba_chunk=32,
        q_chunk=64,
    )
