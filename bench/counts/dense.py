"""Counts for a dense decoder: GQA attention with q/k/v bias, RoPE,
SwiGLU MLP, RMSNorm, tied or untied output head.

Parameters are stored in bfloat16 (2 bytes); the optimizer's moments are
float32.  A FLOP is one multiply or one add, so a multiply-accumulate is 2.
"""
BF16, F32 = 2, 4


def _dims(c):
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    return d, ff, H, KV, Dh, c["num_hidden_layers"], c["vocab_size"]


def layer_matmul_params(c) -> int:
    d, ff, H, KV, Dh, _, _ = _dims(c)
    return d * H * Dh + 2 * d * KV * Dh + H * Dh * d + 3 * d * ff


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def n_params(c) -> int:
    d, ff, H, KV, Dh, L, V = _dims(c)
    per_layer = layer_matmul_params(c) + (H + 2 * KV) * Dh + 2 * d
    embed = V * d
    head = 0 if c["tie_word_embeddings"] else head_params(c)
    return L * per_layer + embed + head + d


def matmul_params(c) -> int:
    """Weights a token multiplies through: every projection and the
    output head (a tied head counts once, as the head)."""
    return c["num_hidden_layers"] * layer_matmul_params(c) + head_params(c)


def attention_flops(c, n_keys: float) -> float:
    """Forward FLOPs of one query token attending ``n_keys`` keys:
    scores and the weighted sum, every layer and head."""
    _, _, H, _, Dh, L, _ = _dims(c)
    return 4.0 * n_keys * H * Dh * L


def train_flops_per_token(c, seq_len: int) -> float:
    """Forward and backward (3x forward), causal attention over an average
    of (S + 1) / 2 keys, no recomputation."""
    fwd = 2.0 * matmul_params(c) + attention_flops(c, (seq_len + 1) / 2.0)
    return 3.0 * fwd


def prefill_flops(c, prompt_len: int) -> float:
    """Forward over a prompt; only the last position needs the head."""
    d, V = c["hidden_size"], c["vocab_size"]
    body = 2.0 * (matmul_params(c) - head_params(c)) * prompt_len
    attn = attention_flops(c, (prompt_len + 1) / 2.0) * prompt_len
    return body + attn + 2.0 * d * V


def decode_flops(c, pos: int) -> float:
    """One new token at absolute position ``pos`` (attends pos + 1 keys)."""
    return 2.0 * matmul_params(c) + attention_flops(c, pos + 1)


def kv_bytes_per_position(c) -> int:
    _, _, _, KV, Dh, L, _ = _dims(c)
    return L * 2 * KV * Dh * BF16


def decode_weight_bytes(c, n_rows: int) -> int:
    """Weights one decode step reads: every matrix once, plus the
    embedding rows of the ``n_rows`` tokens when the head is untied (a tied
    head reads the whole table anyway)."""
    total = n_params(c) * BF16
    if not c["tie_word_embeddings"]:
        total += -c["vocab_size"] * c["hidden_size"] * BF16 \
            + n_rows * c["hidden_size"] * BF16
    return total


def update_bytes(c) -> int:
    """One delayed Adam update: read p, g, m, v, gbuf and write p, m, v,
    gbuf.  Every parameter here is bfloat16: 2+2+4+4+2 read, 2+4+4+2
    written."""
    return n_params(c) * (2 + 2 + 4 + 4 + 2 + 2 + 4 + 4 + 2)
