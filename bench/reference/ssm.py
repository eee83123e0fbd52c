"""Plain float32 reference of a Mamba-2 stack (SSD, one group of B/C
shared by every head), with the scan written as the sequential
recurrence it stands for:

    h_t = exp(dt_t · A) · h_{t−1} + (dt_t · x_t) ⊗ B_t,    y_t = h_t · C_t

Per layer: RMSNorm, in-projections of z, x, B, C and dt, a depthwise
causal conv with bias and SiLU over (x, B, C), dt = softplus(dt + dt_bias),
A = −exp(A_log), the recurrence, the skip D·x, the gated RMSNorm of
y · SiLU(z), and the out-projection.  Then a final RMSNorm and the head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, rms_norm, weighted_xent_sums

LOSS_BLOCK = 256
SCAN_UNROLL = 8


def declare(c) -> dict:
    s = c["ssm_cfg"]
    d, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    di = s["expand"] * d
    N, K = s["d_state"], s["d_conv"]
    H = di // s["headdim"]
    bf, f32 = "bfloat16", "float32"
    decl = {
        "embed": ((V, d), "normal", bf),
        "final_norm": ((d,), "ones", bf),
        "blocks": {"mamba": {
            "norm": ((L, d), "ones", bf),
            "in_z": ((L, d, di), "fan_in", bf),
            "in_x": ((L, d, di), "fan_in", bf),
            "in_B": ((L, d, N), "fan_in", bf),
            "in_C": ((L, d, N), "fan_in", bf),
            "in_dt": ((L, d, H), "fan_in", bf),
            "conv_w": ((L, K, di + 2 * N), "fan_in", bf),
            "conv_b": ((L, di + 2 * N), "zeros", bf),
            "A_log": ((L, H), "mamba_A", f32),
            "D": ((L, H), "ones", f32),
            "dt_bias": ((L, H), "mamba_dt", f32),
            "gate_norm": ((L, di), "ones", bf),
            "out_proj": ((L, di, d), "fan_in", bf),
        }},
    }
    if not c["tie_embeddings"]:
        decl["lm_head"] = ((d, V), "fan_in", bf)
    return decl


def _recurrence(x, dt, A, Bm, Cm):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) → y (B,S,H,P)."""
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt * A)[:, :, None, None] * h \
            + (xt * dtt[..., None])[..., None] * bt[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, ct,
                             precision=jax.lax.Precision.HIGHEST)

    mv = lambda t: jnp.moveaxis(t, 1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros((Bb, H, P, N), F32),
                         (mv(x), mv(dt), mv(Bm), mv(Cm)),
                         unroll=SCAN_UNROLL)
    return jnp.moveaxis(ys, 0, 1)


def _layer(c, ein, p, h):
    s = c["ssm_cfg"]
    eps = c["rms_norm_eps"]
    di = s["expand"] * c["d_model"]
    N, K, P = s["d_state"], s["d_conv"], s["headdim"]
    H = di // P
    Bb, S, _ = h.shape
    x = rms_norm(h, p["norm"], eps)
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
    A = -jnp.exp(p["A_log"])
    xh = xi.reshape(Bb, S, H, P)
    y = _recurrence(xh, dt, A, Bm, Cm) + xh * p["D"][:, None]
    y = rms_norm(y.reshape(Bb, S, di) * jax.nn.silu(z), p["gate_norm"], eps)
    return h + ein("bse,ed->bsd", y, p["out_proj"])


def hidden(c, ein, params, tokens):
    h = params["embed"][tokens]
    layer = jax.checkpoint(lambda p, x: _layer(c, ein, p, x))
    h, _ = jax.lax.scan(lambda x, p: (layer(p["mamba"], x), None), h,
                        params["blocks"])
    return rms_norm(h, params["final_norm"], c["rms_norm_eps"])


def _head(c, params):
    return params["embed"].T if c["tie_embeddings"] else params["lm_head"]


def logits(c, ein, params, tokens, lo: int, hi: int):
    h = hidden(c, ein, params, tokens)[:, lo:hi]
    return ein("bsd,dv->bsv", h, _head(c, params))


def loss_sums(c, ein, params, tokens, w_rows):
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
