"""Counts for a Mamba-2 (SSD) stack: in-projections of z, x, B, C and dt,
a depthwise causal conv over (x, B, C), the chunked SSD scan with one
group of B/C shared by all heads, a gated RMSNorm and the out-projection.

Projections and norms are bfloat16; A_log, D and dt_bias are float32.
"""
BF16, F32 = 2, 4


def _dims(c):
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    N, P, K = s["d_state"], s["headdim"], s["d_conv"]
    return d, di, N, di // P, P, K, c["n_layer"], c["vocab_size"]


def layer_matmul_params(c) -> int:
    d, di, N, H, _, K, _, _ = _dims(c)
    conv = K * (di + 2 * N)
    return 2 * d * di + 2 * d * N + d * H + conv + di * d


def head_params(c) -> int:
    return c["d_model"] * c["vocab_size"]


def f32_params(c) -> int:
    _, _, _, H, _, _, L, _ = _dims(c)
    return 3 * H * L


def n_params(c) -> int:
    d, di, N, H, _, _, L, V = _dims(c)
    per_layer = layer_matmul_params(c) + (di + 2 * N) + 3 * H + d + di
    embed = V * d
    head = 0 if c["tie_embeddings"] else head_params(c)
    return L * per_layer + embed + head + d


def matmul_params(c) -> int:
    return c["n_layer"] * layer_matmul_params(c) + head_params(c)


def ssd_flops_per_token(c) -> float:
    """Forward FLOPs of the chunked SSD scan per token and layer, chunk c:
    C·Bᵀ over the causal half of the chunk, its product with x over every
    head, the chunk-end state update, and the inter-chunk read-out."""
    _, _, N, H, P, _, _, _ = _dims(c)
    ch = c["ssm_cfg"]["chunk_size"]
    half = (ch + 1) / 2.0
    return 2.0 * half * N + 2.0 * half * H * P + 2.0 * H * P * N \
        + 2.0 * H * P * N


def train_flops_per_token(c, seq_len: int) -> float:
    fwd = 2.0 * matmul_params(c) + c["n_layer"] * ssd_flops_per_token(c)
    return 3.0 * fwd


def update_bytes(c) -> int:
    """One delayed Adam update over bfloat16 projections (26 bytes a
    parameter) and float32 A_log / D / dt_bias (36 bytes a parameter)."""
    n32 = f32_params(c)
    n16 = n_params(c) - n32
    return n16 * 26 + n32 * (4 + 4 + 4 + 4 + 4 + 4 + 4 + 4 + 4)
