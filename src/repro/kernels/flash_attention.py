"""Flash attention Pallas TPU kernel (causal + sliding-window, GQA-aware).

TPU-native adaptation of the blockwise online-softmax algorithm:

* the grid is (batch, q_head, q_blocks, kv_blocks); on TPU the last grid dim
  iterates sequentially per core, so the running (m, l, acc) state lives in
  VMEM scratch across kv-block steps,
* BlockSpecs tile q/k/v/o as (block_q|block_k, d_head) VMEM slabs — block
  sizes default to 512/512 which keeps the working set
  (2·block·d + block², f32) well under the ~16 MB VMEM budget and keeps the
  MXU matmul dims at multiples of 128,
* fully-masked kv blocks (beyond the causal frontier or the sliding window)
  are skipped with ``pl.when`` — the TPU analogue of warp-level early-exit.

Validated under ``interpret=True`` against ``ref.reference_attention``
(tests/test_kernels.py sweeps shapes, dtypes, GQA ratios, windows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, block_q, block_k, n_kv_blocks, causal, window,
                 seq_q, seq_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip: strictly above the causal diagonal, or entirely
    # behind the sliding window
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= k_start + block_k - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale             # (bq, d)
        k = k_ref[...].astype(jnp.float32)                     # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = (qp < seq_q) & (kp < seq_k)
        if causal:
            ok &= kp <= qp
        if window is not None:
            ok &= kp > qp - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                                    # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(ok, p, 0.0)          # NEG_INF rows would exp→~0 anyway
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_cur

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           block_q=512, block_k=512, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) with H % KV == 0.
    Returns (B, Sq, H, D) in q.dtype.

    The kernel runs head-major: operands are transposed to (B, H, S, D) so
    every block's last two dims are (block, D) — the (8, 128) tiling rule
    of the TPU lowering refuses a block of 1 on the heads axis."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    Sq_pad, Sk_pad = nq * block_q, nk * block_k
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    if Sk_pad != Sk:
        k = jnp.pad(k, ((0, 0), (0, Sk_pad - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_pad - Sk), (0, 0), (0, 0)))
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))    # (B, heads, S, D)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv_blocks=nk, causal=causal, window=window, seq_q=Sq, seq_k=Sk)
    sq = pl.Squeezed()
    q_spec = pl.BlockSpec((sq, sq, block_q, D),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((sq, sq, block_k, D),
                           lambda b, h, qi, ki, g=group: (b, h // g, ki, 0))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)[:, :Sq]
