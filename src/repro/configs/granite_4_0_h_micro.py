"""Granite 4.0-H Micro: Mamba-2 layers with a NoPE GQA layer in each period
of ten, a SwiGLU MLP after every mixer, muP multipliers.
[huggingface.co/ibm-granite/granite-4.0-h-micro, config.json]"""
from repro.configs.base import ModelConfig, SSMConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    arch_type="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,                    # shared_intermediate_size
    vocab_size=100352,
    head_dim=64,
    ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, conv_width=4,
                  chunk_size=256),
    layer_types=PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    rope_theta=0.0,               # position_embedding_type "nope"
    tie_embeddings=True,
    norm="rmsnorm",
    ffn="swiglu",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)


def smoke_config() -> ModelConfig:
    """One whole period (ten layers, the attention layer sixth) at CPU
    widths, every multiplier and NoPE kept, GQA 4/2."""
    return CONFIG.with_(n_layers=10, d_model=128, n_heads=4, n_kv_heads=2,
                        head_dim=32, d_ff=256, vocab_size=512,
                        layer_types=PERIOD,
                        ssm=SSMConfig(state_dim=16, head_dim=32, expand=2,
                                      conv_width=4, chunk_size=32))
