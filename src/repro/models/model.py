"""Model zoo composition: param specs, train forward, prefill, decode.

Every stack is built from ``layers.py`` blocks and scanned (stacked
weights) so the lowered HLO stays compact at 64–81 layers.  Dense, MoE,
SSM, VLM and layer-pattern hybrids share one description (``_layout``): a
scan over periods of layers, each layer a mixer (attention or Mamba-2)
followed by the family's MLP, MoE or nothing.  Two stacks keep layouts of
their own: a shared attention block between Mamba layers (zamba2) and the
encoder-decoder (audio).

Conventions
-----------
* params are a nested dict of arrays; the same tree of :class:`Spec`
  (``param_specs``) carries shapes + logical sharding axes.
* ``batch`` is a dict: tokens (B,S) int32 [+ patches (B,P,dv) for vlm,
  frames (B,S,fd) for audio].
* decode uses ring-buffer KV caches (window = sliding_window or context
  length) and O(1) SSM states; ``cache_specs`` declares the cache tree.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from .specs import Spec, init_tree, abstract_tree, axes_tree, count_params
from . import layers as L


# ============================================================================
# parameter specs
# ============================================================================

def _attn_specs(cfg: ArchConfig, stacked: Optional[int]):
    pre = (stacked,) if stacked else ()
    ax = ("layers",) if stacked else ()
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s = {
        "norm": Spec(pre + (d,), ax + ("embed",), "ones"),
        "wq": Spec(pre + (d, H, Dh), ax + ("embed", "heads", "head"), "fan_in"),
        "wk": Spec(pre + (d, KV, Dh), ax + ("embed", "kv_heads", "head"), "fan_in"),
        "wv": Spec(pre + (d, KV, Dh), ax + ("embed", "kv_heads", "head"), "fan_in"),
        "wo": Spec(pre + (H, Dh, d), ax + ("heads", "head", "embed"), "fan_in"),
    }
    if cfg.qkv_bias:
        s["bq"] = Spec(pre + (H, Dh), ax + ("heads", "head"), "zeros")
        s["bk"] = Spec(pre + (KV, Dh), ax + ("kv_heads", "head"), "zeros")
        s["bv"] = Spec(pre + (KV, Dh), ax + ("kv_heads", "head"), "zeros")
    if cfg.qk_norm:
        s["q_norm"] = Spec(pre + (Dh,), ax + ("head",), "ones")
        s["k_norm"] = Spec(pre + (Dh,), ax + ("head",), "ones")
    return s


def _mlp_specs(cfg: ArchConfig, stacked: Optional[int], ff: int):
    pre = (stacked,) if stacked else ()
    ax = ("layers",) if stacked else ()
    d = cfg.d_model
    return {
        "norm": Spec(pre + (d,), ax + ("embed",), "ones"),
        "w_gate": Spec(pre + (d, ff), ax + ("embed", "ff"), "fan_in"),
        "w_up": Spec(pre + (d, ff), ax + ("embed", "ff"), "fan_in"),
        "w_down": Spec(pre + (ff, d), ax + ("ff", "embed"), "fan_in"),
    }


def _moe_specs(cfg: ArchConfig, stacked: int):
    pre, ax = (stacked,), ("layers",)
    d, E, fe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    s = {
        "norm": Spec(pre + (d,), ax + ("embed",), "ones"),
        "router": Spec(pre + (d, E), ax + ("embed", "experts"), "fan_in",
                       dtype="float32"),
        "w_gate": Spec(pre + (E, d, fe), ax + ("experts", "embed", "ff"), "fan_in"),
        "w_up": Spec(pre + (E, d, fe), ax + ("experts", "embed", "ff"), "fan_in"),
        "w_down": Spec(pre + (E, fe, d), ax + ("experts", "ff", "embed"), "fan_in"),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * cfg.moe_d_ff
        s["shared"] = _mlp_specs(cfg, stacked, fs)
        del s["shared"]["norm"]  # shares the moe norm
    return s


def _mamba_specs(cfg: ArchConfig, stacked: Optional[int]):
    pre = (stacked,) if stacked else ()
    ax = ("layers",) if stacked else ()
    d, di, N, Hs, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                       cfg.ssm_heads, cfg.ssm_conv)
    conv_dim = di + 2 * N
    return {
        "norm": Spec(pre + (d,), ax + ("embed",), "ones"),
        "in_z": Spec(pre + (d, di), ax + ("embed", "d_inner"), "fan_in"),
        "in_x": Spec(pre + (d, di), ax + ("embed", "d_inner"), "fan_in"),
        "in_B": Spec(pre + (d, N), ax + ("embed", "state"), "fan_in"),
        "in_C": Spec(pre + (d, N), ax + ("embed", "state"), "fan_in"),
        "in_dt": Spec(pre + (d, Hs), ax + ("embed", "ssm_heads"), "fan_in"),
        "conv_w": Spec(pre + (K, conv_dim), ax + ("conv", "d_inner"), "fan_in"),
        "conv_b": Spec(pre + (conv_dim,), ax + ("d_inner",), "zeros"),
        "A_log": Spec(pre + (Hs,), ax + ("ssm_heads",), "mamba_A", dtype="float32"),
        "D": Spec(pre + (Hs,), ax + ("ssm_heads",), "ones", dtype="float32"),
        "dt_bias": Spec(pre + (Hs,), ax + ("ssm_heads",), "mamba_dt", dtype="float32"),
        "gate_norm": Spec(pre + (di,), ax + ("d_inner",), "ones"),
        "out_proj": Spec(pre + (di, d), ax + ("d_inner", "embed"), "fan_in"),
    }


def param_specs(cfg: ArchConfig) -> dict:
    d, V = cfg.d_model, cfg.vocab
    # A multiplied embedding (granite: × 12) drawn at the law's 0.02 makes
    # the input 12× louder than any other model's, and with random weights
    # the tied head then returns the input token, by a wide margin, at
    # nearly every position.  The fan-in law of the one-hot map it is
    # (1/√vocab) keeps the multiplied input near the usual scale.
    embed_init = "normal" if cfg.embedding_multiplier == 1.0 else "fan_in"
    specs: dict = {
        "embed": Spec((V, d), ("vocab", "embed"), embed_init),
        "final_norm": Spec((d,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = Spec((d, V), ("embed", "vocab"), "fan_in")

    nl = cfg.n_layers
    lay = _layout(cfg)
    if lay is not None:
        period, ffn = lay
        n = nl // len(period)
        mixer = {"attn": _attn_specs, "mamba": _mamba_specs}
        specs["blocks"] = {k: mixer[k](cfg, n * period.count(k))
                           for k in dict.fromkeys(period)}
        if ffn == "mlp":
            specs["blocks"]["mlp"] = _mlp_specs(cfg, nl, cfg.d_ff)
        elif ffn == "moe":
            specs["blocks"]["moe"] = _moe_specs(cfg, nl)
    elif cfg.family == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        rem = cfg.n_layers - g * cfg.attn_every
        specs["blocks"] = {"mamba": _mamba_specs(cfg, g * cfg.attn_every)}
        if rem:
            specs["tail"] = {"mamba": _mamba_specs(cfg, rem)}
        specs["shared_attn"] = _attn_specs(cfg, None)
        specs["shared_mlp"] = _mlp_specs(cfg, None, cfg.d_ff)
    elif cfg.family == "audio":
        specs["frontend_proj"] = Spec((cfg.frontend_dim, d), (None, "embed"), "fan_in")
        specs["enc_blocks"] = {"attn": _attn_specs(cfg, cfg.enc_layers),
                               "mlp": _mlp_specs(cfg, cfg.enc_layers, cfg.d_ff)}
        specs["enc_norm"] = Spec((d,), ("embed",), "ones")
        specs["blocks"] = {"attn": _attn_specs(cfg, nl),
                           "cross": _attn_specs(cfg, nl),
                           "mlp": _mlp_specs(cfg, nl, cfg.d_ff)}
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm":
        specs["projector"] = Spec((cfg.vision_dim, d), (None, "embed"), "fan_in")
    return specs


def init_params(cfg: ArchConfig, key) -> dict:
    return init_tree(param_specs(cfg), key)


def n_params(cfg: ArchConfig) -> int:
    return count_params(param_specs(cfg))


def n_active_params(cfg: ArchConfig) -> int:
    """Active parameters per token (MoE counts top_k + shared experts)."""
    if cfg.family != "moe":
        return n_params(cfg)
    total = count_params(param_specs(cfg))
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff * cfg.n_layers
    inactive = (cfg.n_experts - cfg.top_k) * per_expert
    return total - inactive


# ============================================================================
# block applications (sequence / train)
# ============================================================================

def _residual(cfg, h, u):
    """h + residual_multiplier · u: every sub-block's output joins the
    residual stream through here."""
    r = cfg.residual_multiplier
    return h + (u if r == 1.0 else u * r)


def _apply_attn(cfg, p, h, *, causal=True, positions=None, kv_h=None,
                window=None, return_kv=False):
    """Standard pre-norm attention block.  kv_h: cross-attention memory."""
    x = L.rms_norm(h, p["norm"], cfg.norm_eps)
    src = x if kv_h is None else kv_h
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if kv_h is None and positions is not None and cfg.rope:  # self-attn only
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    if cfg.use_flash_attention:
        from ..kernels.ops import flash_attention
        if cfg.attention_multiplier is not None:
            raise NotImplementedError("the flash kernel scales by 1/√d_head")
        o = flash_attention(q, k, v, causal=causal and kv_h is None,
                            window=window)
    else:
        o = L.attention(q, k, v, causal=causal and kv_h is None, window=window,
                        scale=cfg.attention_multiplier)
    out = _residual(cfg, h, jnp.einsum("bshk,hkd->bsd", o, p["wo"]))
    if return_kv:
        return out, (k, v)
    return out


def _apply_mlp(cfg, p, h):
    x = L.rms_norm(h, p["norm"], cfg.norm_eps)
    return _residual(cfg, h, L.swiglu(x, p["w_gate"], p["w_up"], p["w_down"]))


def _apply_moe(cfg, p, h):
    x = L.rms_norm(h, p["norm"], cfg.norm_eps)
    y, aux = L.moe_ffn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                       cfg.top_k, cfg.capacity_factor)
    if "shared" in p:
        sp = p["shared"]
        y = y + L.swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"])
    return _residual(cfg, h, y), aux


def _mamba_inner(cfg, p, x_n):
    """Projections + conv for a normalised input (B,S,d) → ssd operands."""
    di, N, Hs = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = jnp.einsum("bsd,de->bse", x_n, p["in_z"])
    xi = jnp.einsum("bsd,de->bse", x_n, p["in_x"])
    Bp = jnp.einsum("bsd,dn->bsn", x_n, p["in_B"])
    Cp = jnp.einsum("bsd,dn->bsn", x_n, p["in_C"])
    dt = jnp.einsum("bsd,dh->bsh", x_n, p["in_dt"])
    return z, xi, Bp, Cp, dt


def _apply_mamba(cfg, p, h, return_state=False):
    B, S, d = h.shape
    di, N, Hs, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    x_n = L.rms_norm(h, p["norm"], cfg.norm_eps)
    z, xi, Bp, Cp, dt = _mamba_inner(cfg, p, x_n)
    conv_in = jnp.concatenate([xi, Bp, Cp], axis=-1)
    conv_out = L.causal_conv1d(conv_in, p["conv_w"], p["conv_b"])
    xi, Bp, Cp = jnp.split(conv_out, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = _shard_act(xi.reshape(B, S, Hs, P), ("batch", "seq", "ssm_heads", None))
    dt = _shard_act(dt, ("batch", "seq", "ssm_heads"))
    y, hT = L.ssd_chunked(xh, dt, A, Bp, Cp, chunk=min(cfg.ssm_chunk, S),
                          use_kernel=cfg.use_ssd_kernel)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, di).astype(h.dtype)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(h.dtype),
                   p["gate_norm"], cfg.norm_eps)
    out = _residual(cfg, h, jnp.einsum("bse,ed->bsd", y, p["out_proj"]))
    if return_state:
        K = cfg.ssm_conv
        conv_state = conv_in[:, S - (K - 1):, :]
        return out, (conv_state, hT)
    return out


# ============================================================================
# stacks
# ============================================================================

def _shard_act(x, axes=None):
    """Constrain an activation's sharding (no-op outside a mesh context).

    Rank≥3 activations are named (batch, seq, ...) so a Rules variant with
    "seq" in model_priority turns on sequence parallelism (a §Perf lever);
    under the default rules "seq" maps to None — identical behaviour."""
    from ..distributed.sharding import shard_activation
    if axes is None:
        if x.ndim == 3:
            # "seq"/"act_embed" are inert under default rules (not in
            # model_priority); Rules variants opt in to sequence parallelism
            # or Megatron-style embed-sharded residuals
            axes = ("batch", "seq", "act_embed")
        elif x.ndim > 3:
            axes = ("batch", "seq") + (None,) * (x.ndim - 2)
        else:
            axes = ("batch",) + (None,) * (x.ndim - 1)
    return shard_activation(x, axes)


def _constrain_carry(out):
    """Re-pin batch sharding on rank≥2 float carries (scan drops it)."""
    def f(x):
        if hasattr(x, "ndim") and x.ndim >= 2 and jnp.issubdtype(x.dtype, jnp.floating):
            return _shard_act(x)
        return x
    return jax.tree_util.tree_map(f, out)


def _scan(fn, stacked_params, h, remat: bool):
    body = jax.checkpoint(fn) if remat else fn

    def step(carry, p):
        return _constrain_carry(body(p, carry)), None

    h, _ = jax.lax.scan(step, h, stacked_params)
    return h


_MIXERS = {"A": "attn", "M": "mamba"}
_SCOPES = {"attn": "mixer.attention", "mamba": "mixer.mamba"}


def _layout(cfg):
    """The stack as a scan over periods of layers: ``(period, ffn)``, where
    ``period`` names one period's mixer per layer ("attn" | "mamba") and
    ``ffn`` the block after every mixer ("mlp" | "moe" | None).  A dense,
    MoE or SSM stack is a period of one layer.  None for the stacks that
    keep layouts of their own: a shared attention block between Mamba
    layers (zamba2) and the encoder-decoder (audio)."""
    if cfg.family == "audio" or cfg.attn_every:
        return None
    pattern = cfg.layer_pattern or ("M" if cfg.family == "ssm" else "A")
    if cfg.n_layers % len(pattern):
        raise ValueError(f"{cfg.name}: {cfg.n_layers} layers are not whole "
                         f"periods of {pattern!r}")
    ffn = "moe" if cfg.n_experts else ("mlp" if cfg.d_ff else None)
    return tuple(_MIXERS[c] for c in pattern), ffn


def _apply_mixer(cfg, kind, p, h, *, positions, window, return_state=False):
    """A layer's mixer over a sequence; with ``return_state`` also what
    decode continues from (an attention layer's k/v, a Mamba layer's conv
    and SSD state)."""
    with jax.named_scope(_SCOPES[kind]):
        if kind == "attn":
            return _apply_attn(cfg, p, h, positions=positions, window=window,
                               return_kv=return_state)
        return _apply_mamba(cfg, p, h, return_state=return_state)


def _apply_ffn(cfg, ffn, p, h):
    """The block after a mixer: (h, the MoE's aux loss or 0)."""
    if ffn == "mlp":
        with jax.named_scope("mlp"):
            return _apply_mlp(cfg, p, h), 0.0
    if ffn == "moe":
        with jax.named_scope("moe"):
            return _apply_moe(cfg, p, h)
    return h, 0.0


def _scan_periods(cfg, stacks, carry, layer, *, remat=False, pin=False):
    """Run ``layer`` over the stack, one scan step a period.

    ``stacks`` maps each mixer kind to a tree whose leaves stack that kind's
    layers (weights, and for decode the layer's cache), and the ffn kind to
    the ffn weights of every layer.  ``layer(kind, i, p, p_ffn, carry)``
    applies one layer, ``i`` its (traced) index in its kind's stack, and
    returns ``(carry, out)``.  Each layer is one remat unit; ``pin``
    re-pins the carry's batch sharding after every layer (the train
    stack).  Returns ``(carry, {kind: outs stacked over that kind's
    layers})``.

    A kind with one layer a period is scanned over as it is stacked.  A
    kind with several is indexed per layer by its traced index: slicing a
    whole period's block out of the stack would copy it (the block has a
    consumer per layer), where one layer's slice fuses into its matmul."""
    period, ffn = _layout(cfg)
    n = cfg.n_layers // len(period)
    per = {k: period.count(k) for k in dict.fromkeys(period)}
    if ffn is not None:
        per[ffn] = len(period)
    xs = {k: stacks[k] for k, m in per.items() if m == 1}
    fns = {}
    for k in dict.fromkeys(period):
        f = partial(layer, k)
        fns[k] = jax.checkpoint(f) if remat else f

    def weights(k, pg, i):
        if per[k] == 1:
            return pg[k]
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), stacks[k])

    def body(c, inp):
        pg, q = inp
        outs = {k: [] for k in dict.fromkeys(period)}
        for j, k in enumerate(period):
            i = q * per[k] + len(outs[k])
            pf = (weights(ffn, pg, q * len(period) + j) if ffn is not None
                  else None)
            c, y = fns[k](i, weights(k, pg, i), pf, c)
            if pin:
                c = _constrain_carry(c)
            outs[k].append(y)
        return c, {k: v[0] if per[k] == 1 else
                   jax.tree_util.tree_map(lambda *a: jnp.stack(a), *v)
                   for k, v in outs.items()}

    carry, ys = jax.lax.scan(body, carry, (xs, jnp.arange(n)))
    return carry, {k: jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), v) if per[k] > 1 else v
        for k, v in ys.items()}


def _decoder_stack(cfg, params, h, positions, *, window=None, memory=None):
    remat = cfg.remat == "full"
    blocks = params["blocks"]
    lay = _layout(cfg)
    if lay is not None:
        ffn = lay[1]

        def layer(kind, i, p, pf, c):
            x, aux = c
            x = _apply_mixer(cfg, kind, p, x, positions=positions,
                             window=window)
            x, a = _apply_ffn(cfg, ffn, pf, x)
            return (x, aux + a if ffn == "moe" else aux), None

        aux0 = jnp.zeros((), jnp.float32) if ffn == "moe" else None
        (h, aux), _ = _scan_periods(cfg, blocks, (h, aux0), layer,
                                    remat=remat, pin=True)
        return h, (aux / cfg.n_layers if ffn == "moe" else 0.0)
    if cfg.family == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        k = cfg.attn_every
        sa, sm = params["shared_attn"], params["shared_mlp"]
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((g, k) + a.shape[1:]), blocks["mamba"])

        def group(pg, x):
            x = _apply_attn(cfg, sa, x, positions=positions, window=window)
            x = _apply_mlp(cfg, sm, x)

            def inner(pl, y):
                return _apply_mamba(cfg, pl, y)
            # remat the inner layers too: without it each group's backward
            # stores 6 layers of f32 SSD intermediates (~30 GB/dev at 7B)
            return _scan(inner, pg, x, remat)

        f = jax.checkpoint(group) if remat else group
        h, _ = jax.lax.scan(lambda c, p: (f(p, c), None), h, grouped)
        if "tail" in params:
            def inner(pl, y):
                return _apply_mamba(cfg, pl, y)
            h = _scan(inner, params["tail"]["mamba"], h, remat)
        return h, 0.0
    if cfg.family == "audio":
        def f(p, x):
            x = _apply_attn(cfg, p["attn"], x, positions=positions, window=window)
            x = _apply_attn(cfg, p["cross"], x, kv_h=memory)
            return _apply_mlp(cfg, p["mlp"], x)
        return _scan(f, blocks, h, remat), 0.0
    raise ValueError(cfg.family)


def _encoder_stack(cfg, params, frames):
    """Bidirectional encoder over stubbed frame embeddings (audio)."""
    h = jnp.einsum("bsf,fd->bsd", frames.astype(jnp.dtype(cfg.dtype)),
                   params["frontend_proj"])
    positions = jnp.arange(h.shape[1])
    remat = cfg.remat == "full"

    def f(p, x):
        x = _apply_attn(cfg, p["attn"], x, causal=False, positions=positions)
        return _apply_mlp(cfg, p["mlp"], x)

    h = _scan(f, params["enc_blocks"], h, remat)
    return L.rms_norm(h, params["enc_norm"], cfg.norm_eps)


# ============================================================================
# train / prefill forwards
# ============================================================================

def _embed(cfg, params, tokens):
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    m = cfg.embedding_multiplier
    return h if m == 1.0 else h * m


def _unembed(cfg, params, h):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return _shard_act(logits, ("batch", "seq", "vocab"))


def _embed_input(cfg: ArchConfig, params, batch):
    """Shared train/prefill input embedding → (h, cross-attn memory|None)."""
    memory = None
    if cfg.family == "audio":
        memory = _encoder_stack(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    h = _embed(cfg, params, tokens)
    if cfg.family == "vlm":
        patches = jnp.einsum(
            "bpv,vd->bpd", batch["patches"].astype(jnp.dtype(cfg.dtype)),
            params["projector"])
        h = jnp.concatenate([patches, h[:, patches.shape[1]:]], axis=1)
    return _shard_act(h), memory


def forward_logits(cfg: ArchConfig, params, batch, window=None):
    """Full-sequence forward → (logits, aux_loss)."""
    if window is None:
        window = cfg.sliding_window
    h, memory = _embed_input(cfg, params, batch)
    positions = jnp.arange(batch["tokens"].shape[1])
    h, aux = _decoder_stack(cfg, params, h, positions, window=window,
                            memory=memory)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _unembed(cfg, params, h), aux


def loss_fn(cfg: ArchConfig, params, batch, example_weights=None,
            aux_coeff: float = 0.01, window=None):
    """Next-token CE (+ MoE aux).  ``example_weights`` (B,) implements the
    AsGrad worker-participation mask (see distributed.async_trainer)."""
    logits, aux = forward_logits(cfg, params, batch, window=window)
    labels = batch["tokens"][:, 1:]
    lg = logits[:, :-1]
    mask = jnp.ones(labels.shape, jnp.float32)
    if example_weights is not None:
        mask = mask * example_weights[:, None]
    ce = L.softmax_xent(lg, labels, mask)
    return ce + aux_coeff * aux, {"ce": ce, "aux": aux}


# ============================================================================
# prefill: forward + cache emission (feeds decode)
# ============================================================================

def _ring_from_seq(k_seq, v_seq, W: int):
    """(L,B,S,KV,D) stacked per-layer k/v → ring cache (L,B,W,KV·D) of the
    last W tokens, placed at slot = pos mod W, plus the (B, W) positions
    buffer.

    The slots are static, so the ring is a pad (S < W) or a rotation
    (S ≥ W) of the last W tokens: no scatter.  Two sibling scatters into
    zeros here crash the TPU compiler's scatter fusion."""
    B, S = k_seq.shape[1:3]
    take = min(W, S)
    pos = np.arange(S - take, S)
    positions = np.full((B, W), -1, np.int32)
    positions[:, pos % W] = pos

    def ring(x):
        x = x[:, :, S - take:]
        if take < W:                      # slots 0..S-1, the rest empty
            x = jnp.pad(x, ((0, 0), (0, 0), (0, W - take))
                        + ((0, 0),) * (x.ndim - 3))
        else:
            x = jnp.roll(x, S % W, axis=2)
        return x.reshape(x.shape[:3] + (-1,))

    return ring(k_seq), ring(v_seq), jnp.asarray(positions)


def prefill(cfg: ArchConfig, params, batch, ctx_len: Optional[int] = None):
    """Process the prompt, return (last-token logits (B,V), decode cache).

    The cache tree matches ``cache_specs(cfg, B, ctx_len)``; ctx_len defaults
    to the prompt length.
    """
    window = cfg.sliding_window
    tokens = batch["tokens"]
    Bsz, S = tokens.shape
    ctx = ctx_len or S
    W = min(cfg.sliding_window or ctx, ctx)
    h, memory = _embed_input(cfg, params, batch)
    positions = jnp.arange(S)
    cache: dict = {}
    fam = cfg.family
    lay = _layout(cfg)

    if lay is not None:
        ffn = lay[1]

        def layer(kind, i, p, pf, x):
            x, st = _apply_mixer(cfg, kind, p, x, positions=positions,
                                 window=window, return_state=True)
            return _apply_ffn(cfg, ffn, pf, x)[0], st

        h, st = _scan_periods(cfg, params["blocks"], h, layer)
        if "attn" in st:
            kc, vc, posbuf = _ring_from_seq(*st["attn"], W)
            cache = {"self": {"k": kc, "v": vc}, "positions": posbuf}
        if "mamba" in st:
            cache["ssm"] = dict(zip(("conv", "ssd"), st["mamba"]))
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        k = cfg.attn_every
        sa, sm = params["shared_attn"], params["shared_mlp"]
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((g, k) + a.shape[1:]), params["blocks"]["mamba"])

        def fg(x, pg):
            x, kv = _apply_attn(cfg, sa, x, positions=positions, window=window,
                                return_kv=True)
            x = _apply_mlp(cfg, sm, x)

            def fi(y, pl):
                y, st = _apply_mamba(cfg, pl, y, return_state=True)
                return y, st

            x, st = jax.lax.scan(fi, x, pg)
            return x, (kv, st)

        h, (kvs, sts) = jax.lax.scan(fg, h, grouped)
        kc, vc, posbuf = _ring_from_seq(kvs[0], kvs[1], W)
        cs, ss = sts
        cache = {
            "attn": {"k": kc, "v": vc},
            "positions": posbuf,
            "ssm": {"conv": cs.reshape((g * k,) + cs.shape[2:]),
                    "ssd": ss.reshape((g * k,) + ss.shape[2:])},
        }
        if "tail" in params:
            def fi(y, pl):
                y, st = _apply_mamba(cfg, pl, y, return_state=True)
                return y, st

            h, (cs2, ss2) = jax.lax.scan(fi, h, params["tail"]["mamba"])
            cache["ssm_tail"] = {"conv": cs2, "ssd": ss2}
    elif fam == "audio":
        def f(x, p):
            x, kv = _apply_attn(cfg, p["attn"], x, positions=positions,
                                window=window, return_kv=True)
            # cross k/v come from the (un-normed) encoder memory — the block
            # norm applies only to the decoder stream, matching _apply_attn
            ck = jnp.einsum("bsd,dhk->bshk", memory, p["cross"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", memory, p["cross"]["wv"])
            x = _apply_attn(cfg, p["cross"], x, kv_h=memory)
            x = _apply_mlp(cfg, p["mlp"], x)
            return x, (kv, (ck, cv))

        h, (kvs, crosses) = jax.lax.scan(f, h, params["blocks"])
        kc, vc, posbuf = _ring_from_seq(kvs[0], kvs[1], W)
        cache = {"self": {"k": kc, "v": vc}, "positions": posbuf,
                 "cross_k": crosses[0], "cross_v": crosses[1]}
    else:
        raise ValueError(fam)

    h = L.rms_norm(h[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)[:, 0]
    return logits, cache


# ============================================================================
# decode (serve_step)
# ============================================================================

def cache_specs(cfg: ArchConfig, batch: int, ctx_len: int) -> dict:
    """Cache tree as Specs (shapes + logical axes) — feeds input_specs().

    Every leaf carries a batch axis; the (batch, W) positions buffer holds
    the absolute position in each row's ring slot (−1 = empty), so each
    row decodes at its own position.

    A K/V ring is (layers, batch, W, KV·Dh): the heads fold into one minor
    dim, which the chip tiles with W without padding (a (KV, Dh) = (2, 64)
    pair would not be), so a decode step's row writes and its reads agree
    on one layout and the ring is never relayouted.  The model axis shards
    a ring's positions (ctx), never the folded dim: every head's score
    contracts over all of it (``layers.decode_attention``), so splitting it
    would all-reduce the scores.
    """
    W = min(cfg.sliding_window or ctx_len, ctx_len)
    KV, Dh, nl = cfg.n_kv_heads, cfg.d_head, cfg.n_layers
    dt = cfg.dtype
    pos_spec = Spec((batch, W), ("batch", "ctx"), "zeros", "int32")

    def ring(lyrs):
        return {
            "k": Spec((lyrs, batch, W, KV * Dh),
                      ("layers", "batch", "ctx", None), "zeros", dt),
            "v": Spec((lyrs, batch, W, KV * Dh),
                      ("layers", "batch", "ctx", None), "zeros", dt),
        }

    def ssm_states(lyrs):
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        return {
            "conv": Spec((lyrs, batch, cfg.ssm_conv - 1, conv_dim),
                         ("layers", "batch", None, "d_inner"), "zeros", dt),
            "ssd": Spec((lyrs, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                        ("layers", "batch", "ssm_heads", None, None),
                        "zeros", "float32"),
        }

    c: dict = {}
    lay = _layout(cfg)
    if lay is not None:
        period = lay[0]
        n = nl // len(period)
        if "attn" in period:
            c["self"] = ring(n * period.count("attn"))
            c["positions"] = pos_spec
        if "mamba" in period:
            c["ssm"] = ssm_states(n * period.count("mamba"))
    elif cfg.family == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        rem = cfg.n_layers - g * cfg.attn_every
        c["ssm"] = ssm_states(g * cfg.attn_every)
        if rem:
            c["ssm_tail"] = ssm_states(rem)
        c["attn"] = ring(g)
        c["positions"] = pos_spec
    elif cfg.family == "audio":
        c["self"] = ring(nl)
        c["positions"] = pos_spec
        c["cross_k"] = Spec((nl, batch, ctx_len, KV, Dh),
                            ("layers", "batch", "ctx", "kv_heads", "head"),
                            "zeros", dt)
        c["cross_v"] = Spec((nl, batch, ctx_len, KV, Dh),
                            ("layers", "batch", "ctx", "kv_heads", "head"),
                            "zeros", dt)
    return c


def init_cache(cfg: ArchConfig, batch: int, ctx_len: int) -> dict:
    tree = init_tree(cache_specs(cfg, batch, ctx_len), jax.random.PRNGKey(0))
    if "positions" in tree:
        tree["positions"] = tree["positions"] - 1   # −1 = empty slot
    return tree


def _decode_attn(cfg, p, h, kc, vc, cache_positions, pos, window, slot):
    """One-token attention over the ring as it stands plus the token's own
    k/v; returns (h', k, v) with the token's (B, KV·Dh) rows, which
    ``_write_rows`` puts at ``slot`` once every layer has attended.
    ``pos``/``slot`` are (B,): each row's position and ring slot, against
    the (B, W) ``cache_positions``.
    """
    x = L.rms_norm(h, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope:
        posv = pos[:, None]
        q = L.rope(q, posv, cfg.rope_theta)
        k = L.rope(k, posv, cfg.rope_theta)
    o = L.decode_attention(q, kc, vc, cache_positions, pos, k, v, slot,
                           window=window, scale=cfg.attention_multiplier)
    B = k.shape[0]
    return (_residual(cfg, h, jnp.einsum("bshk,hkd->bsd", o, p["wo"])),
            k.reshape(B, -1), v.reshape(B, -1))


def _write_rows(ring, k, v, slot):
    """Write one step's rows into a ring {"k", "v"} of (layers, B, W, KV·Dh):
    ``k``/``v`` (layers, B, KV·Dh) land at each row's ring slot ``slot``
    (B,).  One update per ring for all layers, in place in a donated or
    scan-carried ring.  The write scatters single KV·Dh-wide rows, indexed
    by (layer, row, slot): a scatter whose window spans the layers would
    ask for a layout with the layers minor, and the ring would then be
    relayouted for the reads at every step."""
    def put(c, new):
        nl, B = c.shape[:2]
        li = jnp.broadcast_to(jnp.arange(nl)[:, None], (nl, B))
        bi = jnp.broadcast_to(jnp.arange(B)[None, :], (nl, B))
        si = jnp.broadcast_to(slot[None, :], (nl, B))
        return c.at[li, bi, si].set(new)

    return {"k": put(ring["k"], k), "v": put(ring["v"], v)}


def _decode_cross(cfg, p, h, ck, cv):
    x = L.rms_norm(h, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    o = L.attention(q, ck, cv, causal=False)
    return h + jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _decode_mamba(cfg, p, h, conv_state, ssd_state):
    B = h.shape[0]
    di, N, Hs, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    x_n = L.rms_norm(h, p["norm"], cfg.norm_eps)
    z, xi, Bp, Cp, dt = _mamba_inner(cfg, p, x_n)
    conv_in = jnp.concatenate([xi, Bp, Cp], axis=-1)[:, 0]        # (B, conv_dim)
    y_conv, conv_state = L.conv1d_decode(conv_state, conv_in, p["conv_w"], p["conv_b"])
    xi, Bp, Cp = jnp.split(y_conv, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xi.reshape(B, Hs, P)
    y, ssd_state = L.ssd_decode_step(ssd_state, xh, dt, A, Bp, Cp)
    y = y + xh.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(B, 1, di).astype(h.dtype)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(h.dtype),
                   p["gate_norm"], cfg.norm_eps)
    return (_residual(cfg, h, jnp.einsum("bse,ed->bsd", y, p["out_proj"])),
            conv_state, ssd_state)


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, ctx_len: int):
    """serve_step: ONE new token per sequence against the cache.

    tokens: (B,) int32; pos: (B,) int32, each row's absolute position (a
    scalar is broadcast to every row); every row writes its own ring slot.
    The layer loop only reads the K/V rings; it returns the step's new rows,
    which one ``_write_rows`` per ring stores after the loop.  The recurrent
    state stacks ride in the loop's carry and each Mamba layer updates its
    own slice in place, so a step holds one copy of them.
    Returns (logits (B, V), new_cache).
    """
    W = min(cfg.sliding_window or ctx_len, ctx_len)
    window = cfg.sliding_window
    pos = jnp.broadcast_to(pos, tokens.shape)
    slot = jnp.mod(pos, W)
    h = _embed(cfg, params, tokens[:, None])          # (B,1,d)
    cache = dict(cache)

    if "positions" in cache:
        rows = jnp.arange(tokens.shape[0])
        cache["positions"] = cache["positions"].at[rows, slot].set(
            pos.astype(cache["positions"].dtype))
        cpos = cache["positions"]

    fam = cfg.family
    lay = _layout(cfg)
    if lay is not None:
        ffn = lay[1]
        stacks = dict(params["blocks"])
        if "attn" in stacks:
            stacks["attn"] = (stacks["attn"], cache["self"]["k"],
                              cache["self"]["v"])
        states = ((cache["ssm"]["conv"], cache["ssm"]["ssd"])
                  if "ssm" in cache else None)

        def layer(kind, i, p, pf, c):
            x, st = c
            y = None
            with jax.named_scope(_SCOPES[kind]):
                if kind == "attn":
                    pa, kc, vc = p
                    x, k, v = _decode_attn(cfg, pa, x, kc, vc, cpos, pos,
                                           window, slot)
                    y = (k, v)
                else:
                    conv, ssd = st
                    x, cs, ss = _decode_mamba(
                        cfg, p, x, jax.lax.dynamic_index_in_dim(conv, i, 0,
                                                                False),
                        jax.lax.dynamic_index_in_dim(ssd, i, 0, False))
                    st = (jax.lax.dynamic_update_index_in_dim(conv, cs, i, 0),
                          jax.lax.dynamic_update_index_in_dim(ssd, ss, i, 0))
            return (_apply_ffn(cfg, ffn, pf, x)[0], st), y

        (h, states), ys = _scan_periods(cfg, stacks, (h, states), layer)
        if "attn" in ys:
            cache["self"] = _write_rows(cache["self"], *ys["attn"], slot)
        if states is not None:
            cache["ssm"] = dict(zip(("conv", "ssd"), states))
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        k = cfg.attn_every
        sa, sm = params["shared_attn"], params["shared_mlp"]
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((g, k) + a.shape[1:]), params["blocks"]["mamba"])
        conv_g = cache["ssm"]["conv"].reshape((g, k) + cache["ssm"]["conv"].shape[1:])
        ssd_g = cache["ssm"]["ssd"].reshape((g, k) + cache["ssm"]["ssd"].shape[1:])

        def fg(x, inp):
            pg, kc, vc, csg, ssg = inp
            x, k, v = _decode_attn(cfg, sa, x, kc, vc, cpos, pos, window, slot)
            x = _apply_mlp(cfg, sm, x)

            def fi(y, inner):
                pl, cs, ss = inner
                y, cs, ss = _decode_mamba(cfg, pl, y, cs, ss)
                return y, (cs, ss)

            x, (csg, ssg) = jax.lax.scan(fi, x, (pg, csg, ssg))
            return x, (k, v, csg, ssg)

        h, (ks, vs, cs, ss) = jax.lax.scan(
            fg, h, (grouped, cache["attn"]["k"], cache["attn"]["v"], conv_g, ssd_g))
        cache["attn"] = _write_rows(cache["attn"], ks, vs, slot)
        cache["ssm"] = {"conv": cs.reshape(cache["ssm"]["conv"].shape),
                        "ssd": ss.reshape(cache["ssm"]["ssd"].shape)}
        if "ssm_tail" in cache:
            def fi(y, inner):
                pl, cs2, ss2 = inner
                y, cs2, ss2 = _decode_mamba(cfg, pl, y, cs2, ss2)
                return y, (cs2, ss2)

            h, (cs2, ss2) = jax.lax.scan(
                fi, h, (params["tail"]["mamba"], cache["ssm_tail"]["conv"],
                        cache["ssm_tail"]["ssd"]))
            cache["ssm_tail"] = {"conv": cs2, "ssd": ss2}
    elif fam == "audio":
        def f(x, inp):
            p, kc, vc, ck, cv = inp
            x, k, v = _decode_attn(cfg, p["attn"], x, kc, vc, cpos, pos,
                                   window, slot)
            x = _decode_cross(cfg, p["cross"], x, ck, cv)
            x = _apply_mlp(cfg, p["mlp"], x)
            return x, (k, v)

        h, (ks, vs) = jax.lax.scan(
            f, h, (params["blocks"], cache["self"]["k"], cache["self"]["v"],
                   cache["cross_k"], cache["cross_v"]))
        cache["self"] = _write_rows(cache["self"], ks, vs, slot)
    else:
        raise ValueError(fam)

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)[:, 0]
    return logits, cache


# ============================================================================
# batch specs (what input_specs() builds on)
# ============================================================================

def batch_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    """Train/prefill batch as Specs (logical axes drive sharding)."""
    s: dict = {}
    if cfg.family == "audio":
        s["frames"] = Spec((batch, seq, cfg.frontend_dim),
                           ("batch", "seq", None), "normal", "float32")
        s["tokens"] = Spec((batch, max(seq // cfg.dec_ratio, 8)),
                           ("batch", "seq"), "zeros", "int32")
    else:
        s["tokens"] = Spec((batch, seq), ("batch", "seq"), "zeros", "int32")
        if cfg.family == "vlm":
            npatch = min(cfg.n_patches, max(seq // 4, 4))
            s["patches"] = Spec((batch, npatch, cfg.vision_dim),
                                ("batch", "seq", None), "normal", "float32")
    return s
