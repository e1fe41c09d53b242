"""granite-4.0-h-micro [hybrid]: 40L d2048, Mamba-2 SSD and NoPE GQA
attention (32H, kv=8) 9:1, a SwiGLU MLP (ff8192) after every mixer,
v100352, granite's multipliers
[hf:ibm-granite/granite-4.0-h-micro]."""
from repro.models import ModelConfig, SSMCfg

#: the period of 10 layers: attention at position 5 (layers 5, 15, 25, 35)
PATTERN = (("ssd", "dense"),) * 5 + (("attn", "dense"),) + (("ssd", "dense"),) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab_size=100352, head_dim=64,
    pattern=PATTERN,
    ssm=SSMCfg(d_state=128, head_dim=64, expand=2, chunk=256, conv_width=4,
               n_groups=1),
    tie_embeddings=True,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    position_embedding_type="nope",
)


def smoke_config() -> ModelConfig:
    return CONFIG.scaled(n_layers=20, d_model=64, n_heads=4, n_kv_heads=2,
                         d_ff=128, vocab_size=256, head_dim=16,
                         ssm=SSMCfg(d_state=16, head_dim=16, expand=2,
                                    chunk=32, conv_width=4, n_groups=1))
