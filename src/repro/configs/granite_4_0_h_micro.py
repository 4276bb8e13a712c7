"""Granite-4.0-H-Micro (3.2B). [hf:ibm-granite/granite-4.0-h-micro]

Hybrid of Mamba-2 mixers and grouped-query attention, dense (no routed
experts): 40 layers, attention at layers 5, 15, 25 and 35 (a period of 10),
every layer followed by a SwiGLU MLP of width 8192.  Mamba-2: 64 heads of
64 (d_inner 4096 = 2 x 2048), d_state 128, one group, conv width 4 with a
bias, chunk 256.  Attention: 32 query and 8 KV heads of 64 (2048 / 32; the
config gives no head_dim) with no positional encoding at all (NoPE).  The
muP-style multipliers are the published ones: embeddings x12, each
sublayer's output x0.22, softmax scale 1/64, logits / 8.  Tied embeddings.
The 4 attention layers keep full K/V, the 36 Mamba layers a constant-size
state.
"""
from repro.configs.base import (ATTN_GLOBAL, MAMBA, MambaConfig, ModelConfig,
                                register)

CONFIG = register(
    ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        citation="hf:ibm-granite/granite-4.0-h-micro",
        num_layers=40,
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100352,
        layer_pattern=(MAMBA, MAMBA, MAMBA, MAMBA, MAMBA, ATTN_GLOBAL,
                       MAMBA, MAMBA, MAMBA, MAMBA),
        mamba=MambaConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                          n_groups=1, chunk_size=256),
        nope=True,
        attention_multiplier=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        mlp_act="silu",
        mlp_gated=True,
        tie_embeddings=True,
        norm_eps=1e-5,
    )
)
