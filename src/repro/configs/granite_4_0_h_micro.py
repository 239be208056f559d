"""granite-4.0-h-micro: Mamba-2 layers with one NoPE GQA layer in every ten,
a shared SwiGLU MLP after each, Granite's multipliers and tied embeddings
[hf:ibm-granite/granite-4.0-h-micro]."""
from repro.configs.base import ModelConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
    vocab_size=100352, head_dim=64,
    layer_types=_PERIOD * 4, position_embedding="nope",
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0, tie_embeddings=True,
    ssm=SSMConfig(d_state=128, d_conv=4, n_ssm_heads=64, head_dim=64,
                  chunk=256, n_groups=1, conv_bias=True),
)
