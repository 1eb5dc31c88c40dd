"""Granite-4.0-H-Micro [hf: ibm-granite/granite-4.0-h-micro, config.json].

40L d_model=2048 — 36 Mamba-2 (SSD) layers and 4 GQA attention layers
(32 query, 8 KV heads of 64, no RoPE), the pattern (mamba x5, attn,
mamba x4) four times; Mamba-2: 64 heads of 64 (d_inner 4096), d_state
128, one group, conv width 4; SwiGLU 8192 in every layer; vocab 100352,
tied; muP multipliers (embedding 12, residual 0.22, logits / 8,
attention scores x 0.015625).

Served with ``admit_rows=4``: an admission prefills four slots, not all
of them.  Its prefill is about 6.4 GFLOP a token, so an all-slots
admission at 32 slots and a 512-token bucket took about 0.9 s on a v5e
(most of it on masked rows), and every waiting request's first token
and every running request's next token waited behind it.
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    head_dim=64,
    pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    mlp_type="swiglu",
    attention_multiplier=0.015625,
    rope=False,
    mamba_heads=64,
    mamba_head_dim=64,
    mamba_d_state=128,
    conv_width=4,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    tie_embeddings=True,
    sub_quadratic=False,
    admit_rows=4,
)
