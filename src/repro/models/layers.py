"""Model building blocks, pure jnp — every assigned family composes these.

All functions are shape-polymorphic and jit/pjit friendly; activations are
bf16 with f32 softmax/normalisation.  Attention auto-switches to a
query-chunked streaming implementation for long sequences so prefill_32k
does not materialise (S, S) score matrices (the Pallas flash kernel in
``repro.kernels`` is the TPU-target version of the same algorithm).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# normalisation / embeddings
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-5):
    x32 = x.astype(F32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(F32)).astype(x.dtype)


def rope(x, positions, theta: float = 1e4):
    """Rotary embeddings.  x: (..., S, H, D); positions: (S,) or (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = positions.astype(F32)[..., None] * freqs          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(Sq, Sk) additive bias from causal + sliding-window constraints."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF).astype(F32)


def _scaled(scores, D, scale):
    """Scores times ``scale``, or over √D where no scale is given."""
    return scores / np.sqrt(D) if scale is None else scores * scale


def _sdpa(q, k, v, bias, scale=None):
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,D), bias: (Sq,Sk) or (B,1,Sq,Sk).

    Operands stay bf16 with f32 accumulation (preferred_element_type) — an
    explicit .astype(F32) would materialise f32 copies of the whole k/v."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=F32)
    scores = _scaled(scores, D, scale)
    if bias.ndim == 2:
        scores = scores + bias[None, None, None]
    else:
        scores = scores + bias[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=F32)
    return out.reshape(B, Sq, H, D).astype(v.dtype)


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              q_offset: int = 0, chunk_q: int = 512, dense_max: int = 1024,
              scale: Optional[float] = None):
    """Self/cross attention with GQA.  Chunked over query blocks when long.
    Scores are q·k · ``scale`` (default 1/√D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Sk)
    if max(Sq, Sk) <= dense_max or Sq < 2 * chunk_q:
        return _sdpa(q, k, v, _mask_bias(q_pos, k_pos, causal, window),
                     scale)

    n_chunks = Sq // chunk_q
    rem = Sq - n_chunks * chunk_q
    qc = q[:, : n_chunks * chunk_q].reshape(B, n_chunks, chunk_q, H, D)
    qc = jnp.moveaxis(qc, 1, 0)                 # (nc, B, cq, H, D)

    @jax.checkpoint  # recompute per-chunk probs in backward (O(chunk) memory)
    def chunk_attn(q_blk, i):
        qp = jnp.arange(chunk_q) + i * chunk_q + q_offset
        ok = jnp.ones((chunk_q, Sk), bool)
        if causal:
            ok &= k_pos[None, :] <= qp[:, None]
        if window is not None:
            ok &= k_pos[None, :] > qp[:, None] - window
        bias = jnp.where(ok, 0.0, NEG_INF).astype(F32)
        return _sdpa(q_blk, k, v, bias, scale)

    def body(_, q_blk_i):
        q_blk, i = q_blk_i
        return None, chunk_attn(q_blk, i)

    _, out = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * chunk_q, H, D)
    if rem:
        tail = _sdpa(q[:, -rem:], k, v,
                     _mask_bias(q_pos[-rem:], k_pos, causal, window), scale)
        out = jnp.concatenate([out, tail], axis=1)
    return out


def decode_attention(q, k_cache, v_cache, cache_positions, pos, k_new, v_new,
                     slot, window: Optional[int] = None,
                     scale: Optional[float] = None):
    """One-token attention vs a ring-buffer cache plus the token's own k/v;
    scores are q·k · ``scale`` (default 1/√D).

    q: (B,1,H,D); caches: (B,W,KV·D), the heads folded into the minor dim;
    cache_positions: (B,W) int32 holding the absolute position stored in
    each row's slot (−1 = empty); pos: (B,) int32, each row's current
    position, so the validity mask is per row; k_new/v_new: (B,1,KV,D), the
    current token's own k/v, which is not in the cache: its score joins the
    ring's in one softmax (a max/sum merge).  ``slot`` (B,) (= pos mod W) is
    the ring row the step overwrites afterwards; it holds position pos − W
    or nothing and is masked, so attention covers exactly the last W
    positions.

    Each query head sits in its KV head's D lanes of the folded dim, zeros
    elsewhere, so one contraction over KV·D scores every head and reads the
    cache in the layout it is stored in (splitting the lanes into (KV, D)
    makes the compiler copy and relayout each layer's ring); the value
    product likewise yields every KV head's mix, of which each query head
    keeps its own.  The zeros add exactly nothing.

    The score tensor is constrained to keep the cache's ctx sharding so
    GSPMD computes a *distributed* softmax (partial max/sum + small
    all-reduce) instead of all-gathering the cache (flash-decode pattern).
    """
    from ..distributed.sharding import shard_activation

    W = k_cache.shape[1]
    valid = (cache_positions >= 0) & (cache_positions <= pos[:, None])
    valid &= jnp.arange(W)[None, :] != slot[:, None]
    if window is not None:
        valid &= cache_positions > (pos[:, None] - window)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(F32)[:, None]  # (B,1=Sq,W)

    B, Sq, H, D = q.shape
    KV = k_new.shape[2]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, D)
    eye = jnp.eye(KV, dtype=q.dtype)[:, None, :, None]    # (KV,1,KV,1)
    qx = (qr[..., None, :] * eye).reshape(B, Sq, KV, G, KV * D)
    scores = _scaled(jnp.einsum("bqkgc,bsc->bkgqs", qx, k_cache,
                                preferred_element_type=F32), D, scale)
    scores = scores + bias[:, None, None]             # (B,1,1,Sq,W)
    scores = shard_activation(scores, ("batch", None, None, None, "ctx"))
    own = _scaled(jnp.einsum("bqkgd,bqkd->bkgq", qr, k_new,
                             preferred_element_type=F32)[..., None], D, scale)
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), own)
    p = jnp.exp(scores - m)
    p_own = jnp.exp(own - m)
    l = jnp.sum(p, axis=-1, keepdims=True) + p_own
    probs = (p / l).astype(v_cache.dtype)
    probs = shard_activation(probs, ("batch", None, None, None, "ctx"))
    out = jnp.einsum("bkgqs,bsc->bkgqc", probs, v_cache,
                     preferred_element_type=F32)
    out = jnp.einsum("bkgqjd,kj->bqkgd", out.reshape(B, KV, G, Sq, KV, D),
                     jnp.eye(KV, dtype=F32))
    out = out + jnp.einsum("bkgq,bqkd->bqkgd",
                           (p_own / l)[..., 0].astype(v_new.dtype), v_new,
                           preferred_element_type=F32)
    return out.reshape(B, Sq, H, D).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("bsd,df->bsf", x, w_gate)
    u = jnp.einsum("bsd,df->bsf", x, w_up)
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, w_down)


# ---------------------------------------------------------------------------
# Mixture of Experts (gather/scatter capacity dispatch)
# ---------------------------------------------------------------------------

def moe_router(x, w_router, top_k: int):
    """Returns (weights (T,k) f32, ids (T,k) i32, aux load-balance loss)."""
    logits = jnp.einsum("td,de->te", x.astype(F32), w_router.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)
    # Switch-style aux loss: E * Σ_e fraction_tokens_e · mean_prob_e
    E = w_router.shape[-1]
    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=F32), axis=0)
    aux = E * jnp.sum(me * fe)
    return w, ids, aux


def moe_ffn(x, w_router, w_gate, w_up, w_down, top_k: int,
            capacity_factor: float = 1.25):
    """Fine-grained top-k MoE over flattened tokens.

    x: (B,S,d);  expert weights: (E, d, f) / (E, f, d).
    Dispatch: per expert, the top-C tokens by routing weight are gathered
    (capacity C = ceil(T·k/E·cf)); overflow tokens are dropped for that
    expert (their residual passes through) — standard capacity semantics.

    Sharding: dispatch is GROUP-LOCAL — tokens are viewed as (G, T/G, d)
    where G = number of data shards; routing, capacity and gather/scatter
    all happen within a group (standard local-capacity MoE), so no
    cross-shard token gather exists.  Expert compute is expert-parallel when
    E divides the model axis (deepseek) and tensor-parallel on the expert ff
    otherwise (grok); the only cross-shard traffic is the combine reduction.
    Without this, GSPMD all-gathers the full token set per layer (≈64 GB/dev
    at grok-1 train scale).
    """
    from ..distributed.sharding import shard_activation, data_shard_count

    B, S, d = x.shape
    E = w_gate.shape[0]
    T = B * S
    G = data_shard_count()
    if T % G or (T // G) < E:
        G = 1
    TL = T // G
    xt = shard_activation(x.reshape(G, TL, d), ("batch", None, None))
    weights, ids, aux = moe_router(xt.reshape(T, d), w_router, top_k)
    weights = weights.reshape(G, TL, top_k)
    ids = ids.reshape(G, TL, top_k)

    C = int(np.ceil(TL * top_k / E * capacity_factor))
    C = min(C, TL)
    # per-token-per-expert routing weight (G, TL, E), 0 if not routed
    w_full = jnp.zeros((G, TL, E), F32)
    garange = jnp.arange(G)[:, None, None]
    w_full = w_full.at[garange, jnp.arange(TL)[None, :, None], ids].set(weights)
    # top-C tokens per expert, within each group
    gate_w, token_idx = jax.lax.top_k(w_full.transpose(0, 2, 1), C)  # (G,E,C)
    x_e = jax.vmap(lambda xg, idx: xg[idx])(xt, token_idx)            # (G,E,C,d)
    x_e = shard_activation(x_e, ("batch", "experts", None, None))
    g = jnp.einsum("gecd,edf->gecf", x_e, w_gate)
    u = jnp.einsum("gecd,edf->gecf", x_e, w_up)
    h = shard_activation(jax.nn.silu(g.astype(F32)).astype(x.dtype) * u,
                         ("batch", "experts", None, "ff"))
    y_e = jnp.einsum("gecf,efd->gecd", h, w_down)                     # (G,E,C,d)
    y_e = shard_activation(y_e, ("batch", "experts", None, None))
    y_e = y_e * gate_w[..., None].astype(y_e.dtype)
    # combine: scatter-add back to token order within each group
    def _combine(idx, ye):
        return jnp.zeros((TL, d), y_e.dtype).at[idx.reshape(-1)].add(
            ye.reshape(E * C, d))

    y = jax.vmap(_combine)(token_idx, y_e)
    y = shard_activation(y, ("batch", None, None))
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Mamba2 (state-space duality, chunked)
# ---------------------------------------------------------------------------

def _segsum(a):
    """a: (..., C).  Returns (..., C, C) with out[i,j] = Σ_{k=j+1..i} a_k for
    j < i, 0 on diagonal, −inf above (the 1-semiseparable log-decay matrix)."""
    C = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((C, C), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, B_, C_, chunk: int = 128, h0=None,
                use_kernel: bool = False):
    """Chunked SSD scan (Mamba2, alg. of Dao & Gu 2024 §6).

    x:  (B, S, H, P)  — per-head inputs
    dt: (B, S, H)     — post-softplus step sizes
    A:  (H,)          — negative decay rates (A = −exp(A_log))
    B_: (B, S, N), C_: (B, S, N)  — shared across heads (n_groups=1)
    h0: optional initial state (B, H, P, N)
    Returns (y (B,S,H,P), h_final (B,H,P,N)).
    """
    Bb, S, H, P = x.shape
    N = B_.shape[-1]
    nc = S // chunk
    assert nc * chunk == S, "seq must divide chunk"
    la = (dt.astype(F32) * A[None, None, :].astype(F32))       # log decay (B,S,H)

    def r(t):  # split the sequence axis into (nc, chunk)
        return t.reshape(t.shape[0], nc, chunk, *t.shape[2:])

    xc, dtc, lac = r(x), r(dt), r(la)                          # lac: (B,k,c,H)
    Bc, Cc = r(B_).astype(F32), r(C_).astype(F32)              # (B,k,c,N)
    xdt = (xc * dtc[..., None]).astype(F32)                    # (B,k,c,H,P)
    cums = jnp.cumsum(lac, axis=2)                             # (B,k,c,H)

    if use_kernel:
        # Pallas intra-chunk kernel (TPU target; interpret on CPU)
        from ..kernels.ops import ssd_chunk
        y_diag, st = ssd_chunk(xc, dtc, A, r(B_), r(C_))
        y_diag = y_diag.astype(F32)
        states = jnp.moveaxis(st, -1, -2)                      # (B,k,H,P,N)
    else:
        # --- intra-chunk (quadratic, attention-like) ---
        # einsum letters: b batch, k chunk, i/j pos-in-chunk, h head, p P, n N
        Lh = jnp.exp(_segsum(jnp.moveaxis(lac, -1, 2)))        # (B,k,H,i,j)
        scores = jnp.einsum("bkin,bkjn->bkij", Cc, Bc)         # CBᵀ, head-shared
        y_diag = jnp.einsum("bkij,bkhij,bkjhp->bkihp", scores, Lh, xdt)

        # --- chunk-final states ---
        decay_to_end = jnp.exp(cums[:, :, -1:, :] - cums)      # (B,k,c,H)
        states = jnp.einsum("bkjn,bkjhp->bkhpn", Bc,
                            xdt * decay_to_end[..., None])     # (B,k,H,P,N)

    # --- inter-chunk recurrence over k (short scan) ---
    chunk_decay = jnp.exp(cums[:, :, -1, :])                   # (B,k,H)


    def step(h, inp):
        s, dec = inp
        h_new = h * dec[..., None, None] + s
        return h_new, h

    init = jnp.zeros((Bb, H, P, N), F32) if h0 is None else h0.astype(F32)
    hT, h_prev = jax.lax.scan(
        step,
        init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)                        # (B,k,H,P,N)

    # --- inter-chunk contribution ---
    decay_from_start = jnp.exp(cums)                           # (B,k,c,H)
    y_off = jnp.einsum("bkin,bkhpn,bkih->bkihp", Cc, h_prev, decay_from_start)
    y = (y_diag + y_off).reshape(Bb, S, H, P)
    return y.astype(x.dtype), hT


def ssd_decode_step(h, x_t, dt_t, A, B_t, C_t):
    """One-token SSD update.  h: (B,H,P,N); x_t: (B,H,P); dt_t: (B,H);
    B_t/C_t: (B,N).  Returns (y (B,H,P), h_new)."""
    a = jnp.exp((dt_t * A[None, :]).astype(F32))               # (B,H)
    upd = jnp.einsum("bhp,bn->bhpn", (x_t * dt_t[..., None]).astype(F32),
                     B_t.astype(F32))
    h_new = h * a[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, C_t.astype(F32))
    return y.astype(x_t.dtype), h_new


# ---------------------------------------------------------------------------
# depthwise causal conv1d (mamba front conv)
# ---------------------------------------------------------------------------

def causal_conv1d(x, w, b=None):
    """x: (B,S,D); w: (K,D) depthwise kernel; left-padded causal."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    if b is not None:
        out = out + b[None, None, :]
    return jax.nn.silu(out.astype(F32)).astype(x.dtype)


def conv1d_decode(conv_state, x_t, w, b=None):
    """conv_state: (B,K−1,D) past inputs; x_t: (B,D).  Returns (y, new_state)."""
    K = w.shape[0]
    full = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,K,D)
    y = jnp.einsum("bkd,kd->bd", full, w)
    if b is not None:
        y = y + b[None, :]
    new_state = full[:, 1:, :]
    return jax.nn.silu(y.astype(F32)).astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """Token-level cross entropy, f32 accumulation.  logits (..., V)."""
    logits = logits.astype(F32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return jnp.sum(nll * mask) / (jnp.sum(mask) + 1e-6)
    return jnp.mean(nll)
