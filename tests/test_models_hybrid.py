"""A stack of mixed layers (granite-4.0-h-micro's smoke variant: one
period ``MMMMMAMMMM``) through the one layer description: the period scan
applies the layers in the published order, and a decode step updates
each Mamba layer's slice of the carried state stacks and writes one K/V
row per attention layer, as a layer-by-layer loop does."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import (decode_step, forward_logits, init_params, prefill)
from repro.models import layers as L
from repro.models import model as M


def _cfg():
    return get_arch("granite-4.0-h-micro").reduced().with_(
        remat="none", dtype="float32")


def _layers(cfg, params):
    """(kind, mixer weights, MLP weights) of every layer in stack order."""
    b, seen = params["blocks"], {"attn": 0, "mamba": 0}
    for j, c in enumerate(cfg.layer_pattern * (cfg.n_layers
                                               // len(cfg.layer_pattern))):
        kind = {"A": "attn", "M": "mamba"}[c]
        pick = lambda t, i: jax.tree_util.tree_map(lambda a: a[i], t)
        yield kind, seen[kind], pick(b[kind], seen[kind]), pick(b["mlp"], j)
        seen[kind] += 1


def test_period_scan_applies_the_layers_in_order():
    cfg = _cfg()
    assert cfg.layer_pattern == "MMMMMAMMMM" and cfg.n_layers == 10
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    got, _ = forward_logits(cfg, params, {"tokens": toks})
    h = M._embed(cfg, params, toks)
    pos = jnp.arange(32)
    for kind, _, pm, pf in _layers(cfg, params):
        h = (M._apply_attn(cfg, pm, h, positions=pos) if kind == "attn"
             else M._apply_mamba(cfg, pm, h))
        h = M._apply_mlp(cfg, pf, h)
    want = M._unembed(cfg, params, L.rms_norm(h, params["final_norm"],
                                              cfg.norm_eps))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_decode_updates_each_layers_state_in_the_stack():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(2))
    ctx, lens = 24, [9, 13]
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab)
    rows = [prefill(cfg, params, {"tokens": toks[b:b + 1, :n]},
                    ctx_len=ctx)[1] for b, n in enumerate(lens)]
    cache = jax.tree_util.tree_map_with_path(
        lambda path, *a: jnp.concatenate(
            a, axis=0 if path[0].key == "positions" else 1), *rows)
    assert set(cache) == {"self", "positions", "ssm"}
    assert cache["ssm"]["ssd"].shape[0] == 9
    assert cache["self"]["k"].shape[0] == 1
    pos = jnp.asarray(lens, jnp.int32)
    tok = jnp.asarray([5, 7], jnp.int32)
    logits, got = jax.jit(lambda c: decode_step(cfg, params, c, tok, pos,
                                                ctx))(cache)
    # the same step, layer by layer in Python
    slot = pos % ctx
    cpos = cache["positions"].at[jnp.arange(2), slot].set(pos)
    h = M._embed(cfg, params, tok[:, None])
    ssd, conv, k_rows, v_rows = [], [], [], []
    for kind, i, pm, pf in _layers(cfg, params):
        if kind == "attn":
            h, k, v = M._decode_attn(cfg, pm, h, cache["self"]["k"][i],
                                     cache["self"]["v"][i], cpos, pos, None,
                                     slot)
            k_rows.append(k)
            v_rows.append(v)
        else:
            h, cs, ss = M._decode_mamba(cfg, pm, h, cache["ssm"]["conv"][i],
                                        cache["ssm"]["ssd"][i])
            conv.append(cs)
            ssd.append(ss)
        h = M._apply_mlp(cfg, pf, h)
    want = M._unembed(cfg, params, L.rms_norm(
        h, params["final_norm"], cfg.norm_eps))[:, 0]
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got["ssm"]["ssd"], jnp.stack(ssd), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got["ssm"]["conv"], jnp.stack(conv),
                               rtol=1e-5, atol=1e-6)
    ring = M._write_rows(cache["self"], jnp.stack(k_rows), jnp.stack(v_rows),
                         slot)
    for kv in ("k", "v"):
        np.testing.assert_allclose(got["self"][kv], ring[kv], rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_array_equal(got["positions"], cpos)


def test_nope_and_multipliers_reach_the_stack():
    """Granite's attention has no positional encoding: in a one-layer
    attention stack the last position's logits do not change when the
    tokens before it are permuted; with RoPE they do.  The embedding,
    residual and logit multipliers each change the logits."""
    base = _cfg().with_(layer_pattern="A", n_layers=1)
    params = init_params(base, jax.random.PRNGKey(4))
    toks = jnp.asarray([[11, 22, 33, 44, 55, 9]], jnp.int32)
    perm = jnp.asarray([[44, 11, 55, 33, 22, 9]], jnp.int32)

    def last(cfg, t):
        return forward_logits(cfg, params, {"tokens": t})[0][0, -1]

    np.testing.assert_allclose(last(base, toks), last(base, perm),
                               rtol=1e-5, atol=1e-6)
    roped = base.with_(rope=True)
    assert not np.allclose(last(roped, toks), last(roped, perm), atol=1e-4)
    for field, value in (("embedding_multiplier", 1.0),
                         ("residual_multiplier", 1.0),
                         ("attention_multiplier", None),
                         ("logits_scaling", 1.0)):
        other = base.with_(**{field: value})
        assert not np.allclose(last(base, toks), last(other, toks),
                               atol=1e-4), field
