"""Plain float32 reference of a dense decoder as Qwen2 defines it: token
embedding, then per layer a pre-norm GQA attention block (q/k/v with bias,
rotary position embedding with the half-split rotation, causal softmax)
and a pre-norm SwiGLU MLP, a final RMSNorm and the output head (tied to
the embedding when the configuration says so).

Weights are declared with the same paths, shapes and init rules the seed
law in :mod:`bench.reference.common` needs.  The layer loop is a scan with
each layer recomputed in the backward pass, and the loss is taken in
blocks of positions, so the reference fits one chip beside nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, rms_norm, weighted_xent_sums

LOSS_BLOCK = 256


def declare(c) -> dict:
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    L, V = c["num_hidden_layers"], c["vocab_size"]
    bf = "bfloat16"
    decl = {
        "embed": ((V, d), "normal", bf),
        "final_norm": ((d,), "ones", bf),
        "blocks": {
            "attn": {
                "norm": ((L, d), "ones", bf),
                "wq": ((L, d, H, Dh), "fan_in", bf),
                "wk": ((L, d, KV, Dh), "fan_in", bf),
                "wv": ((L, d, KV, Dh), "fan_in", bf),
                "wo": ((L, H, Dh, d), "fan_in", bf),
                "bq": ((L, H, Dh), "zeros", bf),
                "bk": ((L, KV, Dh), "zeros", bf),
                "bv": ((L, KV, Dh), "zeros", bf),
            },
            "mlp": {
                "norm": ((L, d), "ones", bf),
                "w_gate": ((L, d, ff), "fan_in", bf),
                "w_up": ((L, d, ff), "fan_in", bf),
                "w_down": ((L, ff, d), "fan_in", bf),
            },
        },
    }
    if not c["tie_word_embeddings"]:
        decl["lm_head"] = ((d, V), "fan_in", bf)
    return decl


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(c, ein, p, h):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    B, S, _ = h.shape
    pos = jnp.arange(S)
    a = p["attn"]
    x = rms_norm(h, a["norm"], eps)
    q = ein("bsd,dhk->bshk", x, a["wq"]) + a["bq"]
    k = ein("bsd,dhk->bshk", x, a["wk"]) + a["bk"]
    v = ein("bsd,dhk->bshk", x, a["wv"]) + a["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(B, S, KV, H // KV, Dh)
    s = ein("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(jnp.float32(Dh))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = ein("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v)
    h = h + ein("bshk,hkd->bsd", o.reshape(B, S, H, Dh), a["wo"])
    m = p["mlp"]
    x = rms_norm(h, m["norm"], eps)
    g = ein("bsd,df->bsf", x, m["w_gate"])
    u = ein("bsd,df->bsf", x, m["w_up"])
    return h + ein("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"])


def hidden(c, ein, params, tokens):
    """Final-normed hidden states (B, S, d) of ``tokens``."""
    h = params["embed"][tokens]
    layer = jax.checkpoint(lambda p, x: _layer(c, ein, p, x))
    h, _ = jax.lax.scan(lambda x, p: (layer(p, x), None), h,
                        params["blocks"])
    return rms_norm(h, params["final_norm"], c["rms_norm_eps"])


def _head(c, params):
    return params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]


def logits(c, ein, params, tokens, lo: int, hi: int):
    """Logits (B, hi − lo, V) at positions [lo, hi)."""
    h = hidden(c, ein, params, tokens)[:, lo:hi]
    return ein("bsd,dv->bsv", h, _head(c, params))


def loss_sums(c, ein, params, tokens, w_rows):
    """(Σ row-weighted next-token NLL, Σ weights) of a block of rows; the
    head and its softmax are taken LOSS_BLOCK positions at a time."""
    h = hidden(c, ein, params, tokens)
    head = _head(c, params)
    S = tokens.shape[1] - 1
    tot = jnp.zeros((), F32)
    wsum = jnp.zeros((), F32)

    @jax.checkpoint
    def block(hb, lb):
        return weighted_xent_sums(ein("bsd,dv->bsv", hb, head), lb, w_rows)

    for lo in range(0, S, LOSS_BLOCK):
        hi = min(lo + LOSS_BLOCK, S)
        a, b = block(h[:, lo:hi], tokens[:, lo + 1:hi + 1])
        tot, wsum = tot + a, wsum + b
    return tot, wsum
