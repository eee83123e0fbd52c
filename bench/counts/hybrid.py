"""Counts for a stack of Mamba-2 and attention layers as Granite 4.0-H
lays it out: per entry of ``layer_types`` a Mamba-2 mixer (as
:mod:`bench.counts.ssm` counts it) or GQA attention with no bias and no
positional encoding, each followed by a SwiGLU MLP; RMSNorms; a tied or
untied head.

Projections, norms and the embedding are bfloat16; A_log, D and dt_bias
are float32.  The decode cache holds, for each slot, every Mamba layer's
SSD state in float32 and its conv state in bfloat16, and every attention
layer's K/V rows in bfloat16.  A FLOP is one multiply or one add, so a
multiply-accumulate is 2.
"""
from . import ssm

BF16, F32 = 2, 4


def _dims(c):
    d, H, KV = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    kinds = c["layer_types"]
    return (d, H, KV, d // H, c["mamba_expand"] * d, c["mamba_d_state"],
            c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_conv"],
            kinds.count("mamba"), kinds.count("attention"), len(kinds),
            c["intermediate_size"], c["vocab_size"])


def mamba_matmul_params(c) -> int:
    d, _, _, _, di, N, Hs, _, K, *_ = _dims(c)
    return 2 * d * di + 2 * d * N + d * Hs + K * (di + 2 * N) + di * d


def attn_matmul_params(c) -> int:
    d, H, KV, Dh, *_ = _dims(c)
    return 2 * d * H * Dh + 2 * d * KV * Dh


def mlp_matmul_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def f32_params(c) -> int:
    """A_log, D and dt_bias of every Mamba layer."""
    *_, Hs, _, _, nm, _, _, _, _ = _dims(c)
    return 3 * Hs * nm


def n_params(c) -> int:
    d, _, _, _, di, N, Hs, _, _, nm, na, L, _, V = _dims(c)
    mamba = mamba_matmul_params(c) + (di + 2 * N) + 3 * Hs + d + di
    attn = attn_matmul_params(c) + d
    mlp = mlp_matmul_params(c) + d
    head = 0 if c["tie_word_embeddings"] else head_params(c)
    return nm * mamba + na * attn + L * mlp + V * d + head + d


def matmul_params(c) -> int:
    """Weights a token multiplies through: every projection, the conv
    and the output head (a tied head counts once, as the head)."""
    *_, nm, na, L, _, _ = _dims(c)
    return (nm * mamba_matmul_params(c) + na * attn_matmul_params(c)
            + L * mlp_matmul_params(c) + head_params(c))


def attention_flops(c, n_keys: float) -> float:
    """Forward FLOPs of one query token attending ``n_keys`` keys: scores
    and the weighted sum, every attention layer and head."""
    _, H, _, Dh, *_, na, _, _, _ = _dims(c)
    return 4.0 * n_keys * H * Dh * na


def ssd_decode_flops(c) -> float:
    """One token's SSD step in every Mamba layer: the state's decay, the
    outer product x·dt ⊗ B and their sum (3 FLOPs an element of the state),
    and the read-out y = h·C (2)."""
    *_, Hs, P, _, nm, _, _, _, _ = _dims(c)
    return 5.0 * Hs * P * c["mamba_d_state"] * nm


def decode_flops(c, pos: int) -> float:
    """One new token at absolute position ``pos`` (attends pos + 1 keys)."""
    return 2.0 * matmul_params(c) + attention_flops(c, pos + 1) \
        + ssd_decode_flops(c)


def prefill_flops(c, prompt_len: int) -> float:
    """Forward over a prompt: the chunked SSD scan in every Mamba layer,
    causal attention; only the last position needs the head."""
    d, *_, nm, _, _, _, V = _dims(c)
    body = 2.0 * (matmul_params(c) - head_params(c)) * prompt_len
    attn = attention_flops(c, (prompt_len + 1) / 2.0) * prompt_len
    scan = nm * ssm.ssd_flops_per_token(c) * prompt_len
    return body + attn + scan + 2.0 * d * V


def kv_bytes_per_position(c) -> int:
    """K and V rows of one position: the attention layers only."""
    _, _, KV, Dh, *_, na, _, _, _ = _dims(c)
    return na * 2 * KV * Dh * BF16


def state_bytes_per_slot(c) -> int:
    """One slot's recurrent state: every Mamba layer's SSD state (float32)
    and the last d_conv − 1 inputs of its conv (bfloat16)."""
    _, _, _, _, di, N, Hs, P, K, nm, *_ = _dims(c)
    return nm * (Hs * P * N * F32 + (K - 1) * (di + 2 * N) * BF16)


def weight_bytes(c) -> int:
    n32 = f32_params(c)
    return (n_params(c) - n32) * BF16 + n32 * F32


def decode_weight_bytes(c, n_rows: int) -> int:
    """What one decode step reads and writes besides the K/V rows: every
    weight once, plus the SSD and conv state of ``n_rows`` slots, read and
    written.  An untied head reads the ``n_rows`` rows of the embedding it
    looks up, a tied one the whole table anyway."""
    total = weight_bytes(c) + 2 * n_rows * state_bytes_per_slot(c)
    if not c["tie_word_embeddings"]:
        total += (n_rows - c["vocab_size"]) * c["hidden_size"] * BF16
    return total
