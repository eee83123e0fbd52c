"""Granite-4.0-H-Micro — Mamba-2 and GQA attention layers in one stack.
[huggingface.co/ibm-granite/granite-4.0-h-micro, config.json]

40 layers, d_model 2048, as the period MMMMMAMMMM four times: 36 Mamba-2
mixers (64 heads of 64, d_inner 4096, state 128, one group, conv 4 with
bias, chunk 256) and 4 attention layers (32 query / 8 KV heads of 64, no
bias, no positional encoding); a SwiGLU MLP of 8192 after every mixer.
Embedding × 12, each sub-block's output × 0.22, scores × 1/64, logits / 8.
vocab 100352, tied.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_head=64,
    d_ff=8192,
    vocab=100352,
    layer_pattern="MMMMMAMMMM",
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_chunk=256,
    norm_eps=1e-5,
    tie_embeddings=True,
)
