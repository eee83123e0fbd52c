"""Plain float32 reference of a stack of Mamba-2 and attention layers as
Granite 4.0-H defines it (``granitemoehybrid``): the token embedding times
``embedding_multiplier``; then, per entry of ``layer_types``, a pre-norm
mixer and a pre-norm SwiGLU MLP, each sub-block's output added to the
residual stream times ``residual_multiplier``; a final RMSNorm, the head
(tied to the embedding when the configuration says so) and the logits
divided by ``logits_scaling``.

The mixers:

* ``mamba``: the Mamba-2 mixer of :mod:`bench.reference.ssm` (in-projections
  of z, x, B, C and dt, a depthwise causal conv with bias and SiLU over
  (x, B, C), dt = softplus(dt + dt_bias), A = −exp(A_log), the sequential
  recurrence, the skip D·x, the gated RMSNorm of y · SiLU(z) over all
  lanes, the out-projection), one group of B/C shared by every head;
* ``attention``: causal GQA with no bias and no positional encoding,
  scores q·k · ``attention_multiplier``.

Weights are declared with the program's paths: the Mamba mixers, the
attention mixers and the MLPs are three stacks, each in layer order.  Each
run of consecutive layers of one kind is a scan that picks its layer's
weights out of the stacks by index (slices of the stacks would copy them),
with each layer recomputed in the backward pass, and the loss is taken in
blocks of positions, so the reference fits one chip beside nothing else.
"""
from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp

from .common import F32, rms_norm, weighted_xent_sums
from .ssm import _recurrence

LOSS_BLOCK = 256
#: the stack of ``blocks`` that holds each kind of mixer
STACK = {"mamba": "mamba", "attention": "attn"}


def _dims(c):
    d, H, KV = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    di = c["mamba_expand"] * d
    if (c["mamba_n_groups"] != 1
            or di != c["mamba_n_heads"] * c["mamba_d_head"]):
        raise ValueError("the reference holds one group of B/C and "
                         "mamba_n_heads × mamba_d_head = mamba_expand × d")
    return (d, H, KV, d // H, di, c["mamba_d_state"], c["mamba_n_heads"],
            c["mamba_d_head"], c["mamba_d_conv"])


def declare(c) -> dict:
    d, H, KV, Dh, di, N, Hs, _, K = _dims(c)
    kinds = c["layer_types"]
    nm, na, L = kinds.count("mamba"), kinds.count("attention"), len(kinds)
    ff, V = c["intermediate_size"], c["vocab_size"]
    bf, f32 = "bfloat16", "float32"
    decl = {
        # the program draws a multiplied embedding by the fan-in law
        "embed": ((V, d), "normal" if c["embedding_multiplier"] == 1
                  else "fan_in", bf),
        "final_norm": ((d,), "ones", bf),
        "blocks": {
            "mamba": {
                "norm": ((nm, d), "ones", bf),
                "in_z": ((nm, d, di), "fan_in", bf),
                "in_x": ((nm, d, di), "fan_in", bf),
                "in_B": ((nm, d, N), "fan_in", bf),
                "in_C": ((nm, d, N), "fan_in", bf),
                "in_dt": ((nm, d, Hs), "fan_in", bf),
                "conv_w": ((nm, K, di + 2 * N), "fan_in", bf),
                "conv_b": ((nm, di + 2 * N), "zeros", bf),
                "A_log": ((nm, Hs), "mamba_A", f32),
                "D": ((nm, Hs), "ones", f32),
                "dt_bias": ((nm, Hs), "mamba_dt", f32),
                "gate_norm": ((nm, di), "ones", bf),
                "out_proj": ((nm, di, d), "fan_in", bf),
            },
            "attn": {
                "norm": ((na, d), "ones", bf),
                "wq": ((na, d, H, Dh), "fan_in", bf),
                "wk": ((na, d, KV, Dh), "fan_in", bf),
                "wv": ((na, d, KV, Dh), "fan_in", bf),
                "wo": ((na, H, Dh, d), "fan_in", bf),
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


def _mamba(c, ein, p, x):
    _, _, _, _, di, N, Hs, P, K = _dims(c)
    Bb, S, _ = x.shape
    z = ein("bsd,de->bse", x, p["in_z"])
    xbc = jnp.concatenate([ein("bsd,de->bse", x, p["in_x"]),
                           ein("bsd,dn->bsn", x, p["in_B"]),
                           ein("bsd,dn->bsn", x, p["in_C"])], -1)
    dt = ein("bsd,dh->bsh", x, p["in_dt"])
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xi, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    xh = xi.reshape(Bb, S, Hs, P)
    y = _recurrence(xh, dt, -jnp.exp(p["A_log"]), Bm, Cm) \
        + xh * p["D"][:, None]
    y = rms_norm(y.reshape(Bb, S, di) * jax.nn.silu(z), p["gate_norm"],
                 c["rms_norm_eps"])
    return ein("bse,ed->bsd", y, p["out_proj"])


def _attention(c, ein, p, x):
    _, H, KV, Dh, *_ = _dims(c)
    B, S, _ = x.shape
    q = ein("bsd,dhk->bshk", x, p["wq"]).reshape(B, S, KV, H // KV, Dh)
    k = ein("bsd,dhk->bshk", x, p["wk"])
    v = ein("bsd,dhk->bshk", x, p["wv"])
    s = ein("bqkgd,bskd->bkgqs", q, k) * c["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = ein("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v)
    return ein("bshk,hkd->bsd", o.reshape(B, S, H, Dh), p["wo"])


def _layer(c, ein, kind, pm, pf, h):
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    h = h + r * mixer(c, ein, pm, rms_norm(h, pm["norm"], eps))
    x = rms_norm(h, pf["norm"], eps)
    g = ein("bsd,df->bsf", x, pf["w_gate"])
    u = ein("bsd,df->bsf", x, pf["w_up"])
    return h + r * ein("bsf,fd->bsd", jax.nn.silu(g) * u, pf["w_down"])


def hidden(c, ein, params, tokens):
    """Final-normed hidden states (B, S, d) of ``tokens``."""
    h = params["embed"][tokens] * c["embedding_multiplier"]
    b = params["blocks"]
    lo, seen = 0, {"mamba": 0, "attention": 0}
    for kind, run in itertools.groupby(c["layer_types"]):
        n = len(list(run))
        layer = jax.checkpoint(partial(_layer, c, ein, kind))

        def step(x, i, kind=kind, layer=layer, m0=seen[kind], l0=lo):
            pick = lambda tree, j: jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, False), tree)
            return layer(pick(b[STACK[kind]], m0 + i), pick(b["mlp"], l0 + i),
                         x), None

        h, _ = jax.lax.scan(step, h, jnp.arange(n))
        lo, seen[kind] = lo + n, seen[kind] + n
    return rms_norm(h, params["final_norm"], c["rms_norm_eps"])


def _head(c, params):
    return params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]


def _logits(c, ein, h, head):
    return ein("bsd,dv->bsv", h, head) / c["logits_scaling"]


def logits(c, ein, params, tokens, lo: int, hi: int):
    """Logits (B, hi − lo, V) at positions [lo, hi)."""
    h = hidden(c, ein, params, tokens)[:, lo:hi]
    return _logits(c, ein, h, _head(c, params))


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
        return weighted_xent_sums(_logits(c, ein, hb, head), lb, w_rows)

    for lo in range(0, S, LOSS_BLOCK):
        hi = min(lo + LOSS_BLOCK, S)
        a, b = block(h[:, lo:hi], tokens[:, lo + 1:hi + 1])
        tot, wsum = tot + a, wsum + b
    return tot, wsum
