"""Granite-4.0-H-Micro — hybrid: Mamba-2 layers with one GQA attention layer
in ten, each mixer followed by a SwiGLU MLP; NoPE, muP-style multipliers,
tied vocabulary.  [hf:ibm-granite/granite-4.0-h-micro]"""
from repro.configs.base import ArchConfig, SSMConfig

# attention at layers 5, 15, 25, 35 of 40: a period of ten
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="hf:ibm-granite/granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,               # 2048 / 32
    d_ff=8192,
    vocab_size=100352,
    ffn_kind="swiglu",
    attention="full",
    position_embedding="nope",
    attn_scale=0.015625,       # attention_multiplier
    layer_types=PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, chunk=256, d_conv=4,
                  conv_bias=True, gated_norm=True),
    tie_embeddings=True,
    dtype="float32",
)
