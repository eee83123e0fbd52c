"""Fused AsGrad server-update kernels (Pallas TPU).

The paper's hot loop is the server update x_{t+1} = x_t − γ g_{i_t}(x_{π_t})
(eq. 2).  In the production tier the stale gradient lives in the delayed
buffer; a naive implementation reads p, gbuf, g and writes p', gbuf' in
FIVE separate HBM passes (sub + copy + clip-scale).  These kernels fuse the
whole update into ONE pass per tile:

* ``async_update``: p' = p − (lr·delay_scale·clip_scale)·gbuf; gbuf' = g.
* ``fused_adam``:   full Adam step (m, v updates + parameter step) with the
  delayed gradient, f32 moments, bf16-safe parameter update.
* ``fused_adam_delayed``: ``fused_adam`` on the stale buffer PLUS the
  gbuf' = g swap in the same grid — the ``delay_rounds > 0`` production
  apply behind ``repro.optim.make_delayed_apply``.
* ``sgd_momentum_step`` / ``sgd_momentum_delayed``: heavy-ball SGD with the
  f32 momentum buffer riding the same HBM pass (m' = μ·m + clip·g;
  p' = p − lr·scale·m'), the latter with the gbuf' = g swap fused in.

Tiling: flat parameter tensors are viewed as (rows, LANE) with LANE=128
(the TPU lane width); BlockSpec tiles (block_rows, 128) keep each operand
slab in VMEM.  Scalars (lr·scales, bias corrections) arrive via a small
SMEM block, the standard scalar-plumbing pattern.  A tensor whose size is
already a whole number of tiles and whose minor dim is LANE (the pooled
layout of :mod:`repro.optim.pool`) is viewed without a copy, and every
state operand is aliased to its output (``input_output_aliases``), so a
donated state is updated in place.

Validated under interpret=True against ``ref.reference_async_update`` /
``ref.reference_fused_adam``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
#: default tile height: one grid step streams (BLOCK_ROWS, LANE) slabs
BLOCK_ROWS = 256
F32 = jnp.float32


def _pad_to_tiles(x, block_rows):
    n = x.size
    per_tile = block_rows * LANE
    tiles = pl.cdiv(n, per_tile)
    padded = tiles * per_tile
    flat = jnp.ravel(x)
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(tiles * block_rows, LANE), tiles


def _swap_alias(gbuf2, fresh_dtype, i_in: int, i_out: int) -> dict:
    """The gbuf → gbuf' alias, when the buffer already holds the fresh
    gradient's dtype (an alias must keep shape and dtype)."""
    return {i_in: i_out} if gbuf2.dtype == jnp.dtype(fresh_dtype) else {}


def _async_update_kernel(scal_ref, p_ref, gbuf_ref, g_ref, p_out, gbuf_out):
    eff = scal_ref[0]
    p = p_ref[...]
    stale = gbuf_ref[...].astype(F32)
    p_out[...] = (p.astype(F32) - eff * stale).astype(p_out.dtype)
    gbuf_out[...] = g_ref[...].astype(gbuf_out.dtype)


def async_update_pallas(params, gbuf, grads, *, lr, clip_scale=1.0,
                        delay_scale=1.0, block_rows=BLOCK_ROWS,
                        interpret=False):
    """Fused delayed-gradient apply on one flat tensor.

    params/gbuf/grads: same shape & dtype.  Returns (p', gbuf')."""
    assert params.shape == gbuf.shape == grads.shape
    shape, dtype = params.shape, params.dtype
    p2, tiles = _pad_to_tiles(params, block_rows)
    b2, _ = _pad_to_tiles(gbuf, block_rows)
    g2, _ = _pad_to_tiles(grads, block_rows)
    eff = jnp.asarray([lr * clip_scale * delay_scale], F32)

    p_new, gbuf_new = pl.pallas_call(
        _async_update_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, dtype),
            jax.ShapeDtypeStruct(b2.shape, grads.dtype),
        ],
        input_output_aliases={1: 0, **_swap_alias(b2, grads.dtype, 2, 1)},
        interpret=interpret,
    )(eff, p2, b2, g2)
    n = params.size
    return (p_new.ravel()[:n].reshape(shape),
            gbuf_new.ravel()[:n].reshape(shape))


def _sgd_step_kernel(scal_ref, p_ref, g_ref, p_out):
    eff = scal_ref[0]
    p_out[...] = (p_ref[...].astype(F32)
                  - eff * g_ref[...].astype(F32)).astype(p_out.dtype)


def sgd_step_pallas(params, grads, *, lr, clip_scale=1.0, delay_scale=1.0,
                    block_rows=BLOCK_ROWS, interpret=False):
    """Plain fused SGD step on one flat tensor: p' = p − eff·g, no buffer.

    The swap-free sibling of ``async_update`` for the NON-delayed path —
    a pallas_call output cannot be dead-code-eliminated, so reusing the
    delayed kernel there would pay a discarded gbuf' write per leaf."""
    assert params.shape == grads.shape
    shape, dtype = params.shape, params.dtype
    p2, tiles = _pad_to_tiles(params, block_rows)
    g2, _ = _pad_to_tiles(grads, block_rows)
    eff = jnp.asarray([lr * clip_scale * delay_scale], F32)

    p_new = pl.pallas_call(
        _sgd_step_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(p2.shape, dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(eff, p2, g2)
    return p_new.ravel()[:params.size].reshape(shape)


def _sgd_momentum_kernel(scal_ref, p_ref, m_ref, g_ref, p_out, m_out,
                         *, momentum):
    lr_eff = scal_ref[0]          # lr · delay_scale
    clip = scal_ref[1]
    m = momentum * m_ref[...] + clip * g_ref[...].astype(F32)
    p_out[...] = (p_ref[...].astype(F32) - lr_eff * m).astype(p_out.dtype)
    m_out[...] = m


def sgd_momentum_step_pallas(params, m, grads, *, lr, momentum,
                             clip_scale=1.0, delay_scale=1.0,
                             block_rows=BLOCK_ROWS, interpret=False):
    """Fused heavy-ball SGD on one flat tensor: m' = μ·m + clip·g,
    p' = p − lr·delay_scale·m'.  m is f32.  Returns (p', m')."""
    assert params.shape == grads.shape == m.shape
    shape, dtype = params.shape, params.dtype
    p2, tiles = _pad_to_tiles(params, block_rows)
    m2, _ = _pad_to_tiles(m.astype(F32), block_rows)
    g2, _ = _pad_to_tiles(grads, block_rows)
    scal = jnp.stack([jnp.asarray(lr * delay_scale, F32),
                      jnp.asarray(clip_scale, F32)])

    kern = functools.partial(_sgd_momentum_kernel, momentum=momentum)
    p_new, m_new = pl.pallas_call(
        kern,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, dtype),
            jax.ShapeDtypeStruct(m2.shape, F32),
        ],
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(scal, p2, m2, g2)
    n = params.size
    return (p_new.ravel()[:n].reshape(shape),
            m_new.ravel()[:n].reshape(shape))


def _sgd_momentum_delayed_kernel(scal_ref, p_ref, m_ref, gb_ref, g_ref,
                                 p_out, m_out, gbuf_out, *, momentum):
    lr_eff = scal_ref[0]
    clip = scal_ref[1]
    m = momentum * m_ref[...] + clip * gb_ref[...].astype(F32)
    p_out[...] = (p_ref[...].astype(F32) - lr_eff * m).astype(p_out.dtype)
    m_out[...] = m
    gbuf_out[...] = g_ref[...].astype(gbuf_out.dtype)


def sgd_momentum_delayed_pallas(params, m, gbuf, grads, *, lr, momentum,
                                clip_scale=1.0, delay_scale=1.0,
                                block_rows=BLOCK_ROWS, interpret=False):
    """Delayed-buffer heavy-ball SGD, one HBM pass per tile:

        m'    ← μ·m + clip·gbuf        (momentum on the STALE gradient)
        p'    ← p − lr·delay_scale·m'
        gbuf' ← g                      (buffer the fresh one)

    Returns (p', m', gbuf')."""
    assert params.shape == gbuf.shape == grads.shape == m.shape
    shape, dtype = params.shape, params.dtype
    p2, tiles = _pad_to_tiles(params, block_rows)
    m2, _ = _pad_to_tiles(m.astype(F32), block_rows)
    b2, _ = _pad_to_tiles(gbuf, block_rows)
    g2, _ = _pad_to_tiles(grads, block_rows)
    scal = jnp.stack([jnp.asarray(lr * delay_scale, F32),
                      jnp.asarray(clip_scale, F32)])

    kern = functools.partial(_sgd_momentum_delayed_kernel, momentum=momentum)
    p_new, m_new, gbuf_new = pl.pallas_call(
        kern,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, dtype),
            jax.ShapeDtypeStruct(m2.shape, F32),
            jax.ShapeDtypeStruct(b2.shape, grads.dtype),
        ],
        input_output_aliases={1: 0, 2: 1,
                              **_swap_alias(b2, grads.dtype, 3, 2)},
        interpret=interpret,
    )(scal, p2, m2, b2, g2)
    n = params.size
    return (p_new.ravel()[:n].reshape(shape),
            m_new.ravel()[:n].reshape(shape),
            gbuf_new.ravel()[:n].reshape(shape))


def _adam_bias_corrections(beta1, beta2, count):
    """bc computed in f32 exactly like the reference optimizer (count may be
    a traced int32 scalar inside a jitted train step)."""
    c = jnp.asarray(count).astype(F32)
    return 1.0 - beta1 ** c, 1.0 - beta2 ** c


def _fused_adam_kernel(scal_ref, p_ref, m_ref, v_ref, g_ref,
                       p_out, m_out, v_out, *, beta1, beta2, eps):
    lr = scal_ref[0]
    bc1 = scal_ref[1]
    bc2 = scal_ref[2]
    clip = scal_ref[3]
    wd = scal_ref[4]
    g = clip * g_ref[...].astype(F32)
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    step = step + wd * p_ref[...].astype(F32)
    p_out[...] = (p_ref[...].astype(F32)
                  - lr * step).astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


def fused_adam_pallas(p, m, v, g, *, lr, beta1=0.9, beta2=0.95, eps=1e-8,
                      count=1, clip_scale=1.0, weight_decay=0.0,
                      block_rows=BLOCK_ROWS, interpret=False):
    """One fused Adam step on a flat tensor; m/v f32.  Returns (p', m', v').

    ``clip_scale`` is the global-norm clip factor (the norm itself is a tree
    reduction and stays outside); ``count`` may be traced."""
    shape, dtype = p.shape, p.dtype
    p2, tiles = _pad_to_tiles(p, block_rows)
    m2, _ = _pad_to_tiles(m.astype(F32), block_rows)
    v2, _ = _pad_to_tiles(v.astype(F32), block_rows)
    g2, _ = _pad_to_tiles(g, block_rows)
    bc1, bc2 = _adam_bias_corrections(beta1, beta2, count)
    scal = jnp.asarray([lr, bc1, bc2, clip_scale, weight_decay], F32)

    kern = functools.partial(_fused_adam_kernel, beta1=beta1, beta2=beta2,
                             eps=eps)
    p_new, m_new, v_new = pl.pallas_call(
        kern,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, dtype),
            jax.ShapeDtypeStruct(m2.shape, F32),
            jax.ShapeDtypeStruct(v2.shape, F32),
        ],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
    )(scal, p2, m2, v2, g2)
    n = p.size
    return (p_new.ravel()[:n].reshape(shape),
            m_new.ravel()[:n].reshape(shape),
            v_new.ravel()[:n].reshape(shape))


def _fused_adam_delayed_kernel(scal_ref, p_ref, m_ref, v_ref, gb_ref, g_ref,
                               p_out, m_out, v_out, gbuf_out,
                               *, beta1, beta2, eps):
    lr = scal_ref[0]
    bc1 = scal_ref[1]
    bc2 = scal_ref[2]
    clip = scal_ref[3]
    wd = scal_ref[4]
    stale = clip * gb_ref[...].astype(F32)
    m = beta1 * m_ref[...] + (1.0 - beta1) * stale
    v = beta2 * v_ref[...] + (1.0 - beta2) * stale * stale
    step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    step = step + wd * p_ref[...].astype(F32)
    p_out[...] = (p_ref[...].astype(F32)
                  - lr * step).astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v
    gbuf_out[...] = g_ref[...].astype(gbuf_out.dtype)


def fused_adam_delayed_pallas(p, m, v, gbuf, g, *, lr, beta1=0.9, beta2=0.95,
                              eps=1e-8, count=1, clip_scale=1.0,
                              weight_decay=0.0, block_rows=BLOCK_ROWS,
                              interpret=False):
    """Delayed-buffer Adam step, one HBM pass per tile:

        p', m', v' ← Adam(p, m, v; clip·gbuf)     (apply the STALE gradient)
        gbuf'      ← g                             (buffer the fresh one)

    This is the trainer's ``delay_rounds > 0`` hot loop (eq. 2 with Adam):
    the naive path reads/writes gbuf twice (once to apply, once to swap);
    here the swap rides the same grid.  Returns (p', m', v', gbuf')."""
    assert p.shape == gbuf.shape == g.shape
    shape, dtype = p.shape, p.dtype
    p2, tiles = _pad_to_tiles(p, block_rows)
    m2, _ = _pad_to_tiles(m.astype(F32), block_rows)
    v2, _ = _pad_to_tiles(v.astype(F32), block_rows)
    b2, _ = _pad_to_tiles(gbuf, block_rows)
    g2, _ = _pad_to_tiles(g, block_rows)
    bc1, bc2 = _adam_bias_corrections(beta1, beta2, count)
    scal = jnp.asarray([lr, bc1, bc2, clip_scale, weight_decay], F32)

    kern = functools.partial(_fused_adam_delayed_kernel, beta1=beta1,
                             beta2=beta2, eps=eps)
    p_new, m_new, v_new, gbuf_new = pl.pallas_call(
        kern,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, dtype),
            jax.ShapeDtypeStruct(m2.shape, F32),
            jax.ShapeDtypeStruct(v2.shape, F32),
            jax.ShapeDtypeStruct(b2.shape, g.dtype),
        ],
        input_output_aliases={1: 0, 2: 1, 3: 2,
                              **_swap_alias(b2, g.dtype, 4, 3)},
        interpret=interpret,
    )(scal, p2, m2, v2, b2, g2)
    n = p.size
    return (p_new.ravel()[:n].reshape(shape),
            m_new.ravel()[:n].reshape(shape),
            v_new.ravel()[:n].reshape(shape),
            gbuf_new.ravel()[:n].reshape(shape))
