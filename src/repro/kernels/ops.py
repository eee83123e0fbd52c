"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` follows the platform alone: compiled Mosaic kernels on
a TPU backend, the Pallas interpreter anywhere else (the CPU test path).
Each wrapper falls back to the pure-jnp oracle (`ref.py`) when
``use_kernel=False``, which is also what the model code uses on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .async_update import (async_update_pallas, fused_adam_pallas,
                           fused_adam_delayed_pallas)
from .ssd_chunk import ssd_chunk_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "use_kernel", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=512,
                    block_k=512, use_kernel=True, interpret=None):
    if not use_kernel:
        return ref.reference_attention(q, k, v, causal=causal, window=window)
    if interpret is None:
        interpret = _interpret_default()
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("lr", "clip_scale", "delay_scale",
                                   "use_kernel", "interpret"))
def async_update(params, gbuf, grads, *, lr, clip_scale=1.0, delay_scale=1.0,
                 use_kernel=True, interpret=None):
    if not use_kernel:
        return ref.reference_async_update(params, gbuf, grads, lr=lr,
                                          clip_scale=clip_scale,
                                          delay_scale=delay_scale)
    if interpret is None:
        interpret = _interpret_default()
    return async_update_pallas(params, gbuf, grads, lr=lr,
                               clip_scale=clip_scale,
                               delay_scale=delay_scale, interpret=interpret)


@partial(jax.jit, static_argnames=("lr", "beta1", "beta2", "eps", "count",
                                   "use_kernel", "interpret"))
def fused_adam(p, m, v, g, *, lr, beta1=0.9, beta2=0.95, eps=1e-8, count=1,
               use_kernel=True, interpret=None):
    if not use_kernel:
        return ref.reference_fused_adam(p, m, v, g, lr=lr, beta1=beta1,
                                        beta2=beta2, eps=eps,
                                        bc1=1 - beta1 ** count,
                                        bc2=1 - beta2 ** count)
    if interpret is None:
        interpret = _interpret_default()
    return fused_adam_pallas(p, m, v, g, lr=lr, beta1=beta1, beta2=beta2,
                             eps=eps, count=count, interpret=interpret)


@partial(jax.jit, static_argnames=("beta1", "beta2", "eps", "weight_decay",
                                   "use_kernel", "interpret"))
def fused_adam_delayed(p, m, v, gbuf, g, *, lr, beta1=0.9, beta2=0.95,
                       eps=1e-8, count=1, clip_scale=1.0, weight_decay=0.0,
                       use_kernel=True, interpret=None):
    """Delayed-buffer Adam + gbuf swap in one pass, on a single flat
    tensor.  ``lr`` / ``count`` / ``clip_scale`` are TRACED (they change
    every step — marking them static would recompile per step); the actual
    trainer hot loop goes through ``repro.optim.make_delayed_apply``, which
    calls the pallas wrapper directly, this is the standalone entry."""
    count = jnp.asarray(count)
    if not use_kernel:
        c = count.astype(jnp.float32)
        return ref.reference_fused_adam_delayed(
            p, m, v, gbuf, g, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
            bc1=1 - beta1 ** c, bc2=1 - beta2 ** c,
            clip_scale=clip_scale, weight_decay=weight_decay)
    if interpret is None:
        interpret = _interpret_default()
    return fused_adam_delayed_pallas(
        p, m, v, gbuf, g, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
        count=count, clip_scale=clip_scale, weight_decay=weight_decay,
        interpret=interpret)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def ssd_chunk(x, dt, A, B_, C_, *, use_kernel=True, interpret=None):
    """Intra-chunk SSD (see ssd_chunk.py for shapes)."""
    if interpret is None:
        interpret = _interpret_default()
    return ssd_chunk_pallas(x, dt, A, B_, C_, interpret=interpret)
