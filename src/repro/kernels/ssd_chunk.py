"""Mamba2 SSD intra-chunk Pallas kernel (state-space duality).

The chunked SSD algorithm's hot spot is the intra-chunk quadratic part —
an attention-like (CBᵀ ∘ L) X contraction plus the chunk-state reduction.
This kernel fuses, per (batch, chunk, head-block) grid cell:

    L      = exp(segsum(dt·A))      (c, c) lower-triangular decay
    scores = (C Bᵀ) ∘ L             (c, c)
    y      = scores @ (x·dt)        (c, P)
    state  = (B · decay_to_end)ᵀ @ (x·dt)   (N, P)   — chunk-final state

so the (c, c) decay/score matrices never touch HBM.  The inter-chunk scan
(S/c steps) stays in jnp — it is tiny and sequential.

Grid: (B·n_chunks, H).  The kernel runs head-major: x is laid out
(B·nc, H, c, P) so its blocks are (c, P) slabs, B/C are (c, N), and the
per-head log-decay prefix sums arrive as a (c, 1) column and a (1, c) row
(computed in the wrapper) — every block's last two dims are whole array
dims, as the TPU lowering's (8, 128) tiling rule requires.

Validated under interpret=True against ``ref.reference_ssd_chunk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _ssd_chunk_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref,
                      y_ref, st_ref, *, chunk):
    x = x_ref[...].astype(F32)                 # (c, P)
    dt = dt_ref[...]                           # (c, 1)
    cum_c = cc_ref[...]                        # (c, 1) Σ dt·A up to row i
    cum_r = cr_ref[...]                        # (1, c) the same, as a row
    Bm = b_ref[...].astype(F32)                # (c, N)
    Cm = c_ref[...].astype(F32)                # (c, N)

    # segsum matrix: cum[i] − cum[j] for j ≤ i, masked to 0 above
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(jj <= ii, jnp.exp(cum_c - cum_r), 0.0)

    xdt = x * dt                               # (c, P)
    cbt = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # C Bᵀ
    y_ref[...] = ((cbt * L) @ xdt).astype(y_ref.dtype)

    decay_to_end = jnp.exp(cum_c[chunk - 1:, :] - cum_c)          # (c, 1)
    st = jax.lax.dot_general(Bm * decay_to_end, xdt,
                             (((0,), (0,)), ((), ())))            # (N, P)
    st_ref[...] = st.astype(st_ref.dtype)


def ssd_chunk_pallas(x, dt, A, B_, C_, *, interpret=False):
    """Intra-chunk SSD for pre-chunked operands.

    x: (B, nc, c, H, P); dt: (B, nc, c, H); A: (H,);
    B_/C_: (B, nc, c, N)  (n_groups = 1, head-shared).
    Returns (y_diag (B,nc,c,H,P), states (B,nc,H,N,P)) — inter-chunk
    recurrence and offset term are composed by the caller (ops.ssd_chunked).
    """
    Bb, nc, c, H, P = x.shape
    N = B_.shape[-1]
    G = Bb * nc

    kern = functools.partial(_ssd_chunk_kernel, chunk=c)
    xr = jnp.swapaxes(x.reshape(G, c, H, P), 1, 2)             # (G, H, c, P)
    dtr = jnp.swapaxes(dt.reshape(G, c, H).astype(F32), 1, 2)  # (G, H, c)
    cum = jnp.cumsum(dtr * A.astype(F32)[None, :, None], axis=-1)
    br = B_.reshape(G, c, N)
    cr = C_.reshape(G, c, N)

    sq = pl.Squeezed()
    col = pl.BlockSpec((sq, sq, c, 1), lambda g, h: (g, h, 0, 0))
    bc = pl.BlockSpec((sq, c, N), lambda g, h: (g, 0, 0))
    y, st = pl.pallas_call(
        kern,
        grid=(G, H),
        in_specs=[
            pl.BlockSpec((sq, sq, c, P), lambda g, h: (g, h, 0, 0)),
            col,
            col,
            pl.BlockSpec((sq, sq, 1, c), lambda g, h: (g, h, 0, 0)),
            bc,
            bc,
        ],
        out_specs=[
            pl.BlockSpec((sq, sq, c, P), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((sq, sq, N, P), lambda g, h: (g, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, H, c, P), x.dtype),
            jax.ShapeDtypeStruct((G, H, N, P), F32),
        ],
        interpret=interpret,
    )(xr, dtr[..., None], cum[..., None], cum[:, :, None, :], br, cr)
    return (jnp.swapaxes(y, 1, 2).reshape(Bb, nc, c, H, P),
            st.reshape(Bb, nc, H, N, P))
