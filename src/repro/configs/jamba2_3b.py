"""AI21-Jamba2-3B — dense hybrid: Mamba-1 with one attention layer in 14.

[hf ai21labs/AI21-Jamba2-3B config.json] 28L d_model=2560 20H over one KV
head of 128, d_ff=8192 (a dense SwiGLU on every layer: num_experts 1),
vocab=65536, tied embeddings. Attention where layer % 14 == 7 (layers 7 and
21), with no positional encoding; Mamba-1 on the other 26 (d_state 16,
d_conv 4, expand 2, dt_rank 160, RMSNorm on dt, B and C).
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    act="swiglu",
    norm_eps=1e-6,
    rope_fraction=0.0,
    tie_embeddings=True,
    attn_every=14,
    attn_offset=7,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=160,
                  inner_norms=True),
    max_seq_len=262144,
)

SMOKE_CONFIG = ModelConfig(
    name="jamba2-smoke",
    family="hybrid",
    n_layers=8,
    d_model=64,
    n_heads=4,
    n_kv_heads=1,
    head_dim=16,
    d_ff=128,
    vocab_size=509,
    act="swiglu",
    norm_eps=1e-6,
    rope_fraction=0.0,
    tie_embeddings=True,
    attn_every=4,
    attn_offset=2,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=8,
                  inner_norms=True),
    max_seq_len=1024,
)
