"""granite-moe-3b-a800m  [moe]
32L d_model=1536 24H (GQA kv=8, head 64) vocab=49155, tied embedding;
MoE of 40 fine-grained SwiGLU experts (d_ff=512 each), top-8, with a
softmax over the 8 chosen router logits; context 4096.  Granite's four
multipliers: embeddings × 12, attention scale 1/64 (in place of
1/sqrt(64)), each residual branch × 0.22, logits ÷ 6.  The checkpoint
is bfloat16; the preset keeps float32 weights, as training needs, and a
deployment serves bfloat16 with ``replace(param_dtype="bfloat16")``
(``launch/serve.py --param-dtype``): 3.3 B parameters are 13.2 GB in
float32, more than one 16 GB chip holds beside a cache.
[hf:ibm-granite/granite-3.0-3b-a800m-base config.json, model_type
granitemoe]
"""
from .base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    d_ff=512,
    vocab=49155,
    period=("attn",),
    mlp="swiglu",
    tie_embeddings=True,
    moe=MoEConfig(num_experts=40, top_k=8),
    rope_theta=10_000.0,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
)


def reduced() -> ModelConfig:
    """The same family at CPU-test widths, multipliers included."""
    return CONFIG.replace(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_ff=32,
        vocab=512, moe=MoEConfig(num_experts=8, top_k=2),
    )
