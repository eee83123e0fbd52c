"""Per-architecture smoke tests: reduced variant of the same family, one
forward + one train-gradient step + one decode step on CPU; asserts output
shapes and absence of NaNs (the brief's required smoke coverage)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models import (
    param_specs, init_params, n_params, n_active_params,
    forward_logits, loss_fn, init_cache, decode_step, batch_specs,
    init_tree, abstract_tree,
)
from repro.models import layers as L, model as M
from repro.models.specs import Spec

ARCH_NAMES = sorted(ARCHS)


def _batch(cfg, B=2, S=32, key=jax.random.PRNGKey(1)):
    specs = batch_specs(cfg, B, S)
    b = {}
    for k, sp in specs.items():
        kk = jax.random.fold_in(key, hash(k) % 1000)
        if sp.dtype == "int32":
            b[k] = jax.random.randint(kk, sp.shape, 0, cfg.vocab, jnp.int32)
        else:
            b[k] = jax.random.normal(kk, sp.shape, jnp.float32)
    return b


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_and_finite(name):
    cfg = get_arch(name).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = jax.jit(lambda p, b: forward_logits(cfg, p, b))(params, batch)
    B = batch["tokens"].shape[0]
    S = batch["tokens"].shape[1]
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_train_step_grad_finite(name):
    cfg = get_arch(name).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)

    @jax.jit
    def step(p, b):
        (l, m), g = jax.value_and_grad(
            lambda q: loss_fn(cfg, q, b), has_aux=True)(p)
        return l, g

    loss, grads = step(params, batch)
    assert np.isfinite(float(loss)) and float(loss) > 0
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in leaves)
    # at least some gradient signal everywhere except unused stubs
    nonzero = sum(float(jnp.abs(g.astype(jnp.float32)).sum()) > 0 for g in leaves)
    assert nonzero / len(leaves) > 0.8


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_step(name):
    cfg = get_arch(name).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, ctx = 2, 16
    cache = init_cache(cfg, B, ctx)
    tok = jnp.array([1, 2], jnp.int32)

    step = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos, ctx))
    logits, cache = step(params, cache, tok, jnp.int32(0))
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # a few more steps reuse the cache without shape drift
    for pos in range(1, 4):
        logits, cache = step(params, cache, tok, jnp.int32(pos))
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_param_counts_match_assignment_scale():
    """Full (non-reduced) configs hit the advertised parameter scale."""
    expect = {
        "grok-1-314b": (250e9, 380e9),
        "deepseek-moe-16b": (13e9, 20e9),
        "minitron-8b": (7e9, 10e9),
        "qwen2-0.5b": (0.3e9, 0.7e9),
        "stablelm-1.6b": (1.2e9, 2.2e9),
        "zamba2-7b": (6e9, 9e9),
        "mamba2-370m": (0.25e9, 0.5e9),
        "seamless-m4t-large-v2": (1.2e9, 2.8e9),
        "pixtral-12b": (10e9, 14e9),
        "qwen3-8b": (6.5e9, 10e9),
    }
    for name, (lo, hi) in expect.items():
        n = n_params(get_arch(name))
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]"


def test_moe_active_params():
    cfg = get_arch("grok-1-314b")
    act = n_active_params(cfg)
    tot = n_params(cfg)
    assert act < tot
    # top-2 of 8 experts → roughly a quarter of expert params active
    assert 0.2 * tot < act < 0.5 * tot


def test_decode_matches_prefill_dense():
    """Sequential decode of a short prompt reproduces full-forward logits."""
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    full, _ = forward_logits(cfg, params, {"tokens": tokens})
    cache = init_cache(cfg, B, S)
    step = jax.jit(lambda c, t, pos: decode_step(cfg, params, c, t, pos, S))
    outs = []
    for pos in range(S):
        lg, cache = step(cache, tokens[:, pos], jnp.int32(pos))
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_decode_matches_prefill_ssm():
    """Same equivalence for the SSD recurrence (chunked scan vs step)."""
    cfg = get_arch("mamba2-370m").reduced().with_(remat="none")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    full, _ = forward_logits(cfg, params, {"tokens": tokens})
    cache = init_cache(cfg, B, S)
    step = jax.jit(lambda c, t, pos: decode_step(cfg, params, c, t, pos, S))
    outs = []
    for pos in range(S):
        lg, cache = step(cache, tokens[:, pos], jnp.int32(pos))
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_sliding_window_attention_restricts_context():
    """With window W, logits for position t only depend on tokens > t−W."""
    cfg = get_arch("qwen3-8b").reduced().with_(sliding_window=4, remat="none",
                                               n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    S = 12
    t1 = jax.random.randint(jax.random.PRNGKey(3), (1, S), 0, cfg.vocab)
    t2 = t1.at[0, 0].set((t1[0, 0] + 7) % cfg.vocab)   # perturb an early token
    l1, _ = forward_logits(cfg, params, {"tokens": t1})
    l2, _ = forward_logits(cfg, params, {"tokens": t2})
    # last position is > W away from position 0 → unchanged
    np.testing.assert_allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]),
                               rtol=1e-4, atol=1e-4)
    # position 1 IS affected
    assert not np.allclose(np.asarray(l1[0, 1]), np.asarray(l2[0, 1]), atol=1e-4)


@pytest.mark.parametrize("name", ["qwen3-8b", "mamba2-370m", "zamba2-7b",
                                  "seamless-m4t-large-v2", "deepseek-moe-16b",
                                  "pixtral-12b", "granite-4.0-h-micro"])
def test_prefill_then_decode_matches_full_forward(name):
    """prefill(prompt) + decode(next tokens) ≡ forward over the whole seq."""
    from repro.models import prefill
    # capacity_factor high enough that no MoE token drops — capacity-based
    # routing otherwise differs legitimately between prompt- and step-batches
    cfg = get_arch(name).reduced().with_(remat="none", capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B=B, S=S)
    full, _ = forward_logits(cfg, params, batch)
    tok = batch["tokens"]
    S_dec = tok.shape[1]          # audio decoders are shorter than S
    split = max(S_dec - 6, S_dec // 2)
    pre_batch = dict(batch)
    pre_batch["tokens"] = tok[:, :split]
    S = S_dec
    last, cache = prefill(cfg, params, pre_batch, ctx_len=S)
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(full[:, split - 1], np.float32),
                               rtol=5e-2, atol=5e-2)
    step = jax.jit(lambda c, t, pos: decode_step(cfg, params, c, t, pos, S))
    for pos in range(split, S):
        lg, cache = step(cache, tok[:, pos], jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(lg, np.float32),
                                   np.asarray(full[:, pos], np.float32),
                                   rtol=7e-2, atol=7e-2)


# ---------------------------------------------------------------------------
# decode step against the write-then-attend oracle
# ---------------------------------------------------------------------------

def _oracle_attn(cfg, p, h, kc, vc, cpos, pos, window, slot):
    """The decode attention as the step used to run it: write the token's
    k/v row into the layer's ring, then attend over the whole ring."""
    x = L.rms_norm(h, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    posv = pos[:, None]
    q, k = L.rope(q, posv, cfg.rope_theta), L.rope(k, posv, cfg.rope_theta)
    B, W = kc.shape[:2]
    rows = jnp.arange(B)
    kc = kc.at[rows, slot].set(k.reshape(B, -1))
    vc = vc.at[rows, slot].set(v.reshape(B, -1))
    valid = (cpos >= 0) & (cpos <= posv)
    if window is not None:
        valid &= cpos > posv - window
    bias = jnp.where(valid, 0.0, L.NEG_INF).astype(jnp.float32)
    bias = bias[:, None, None, None]
    KV, Dh = k.shape[2:]
    qr = q.reshape(B, 1, KV, -1, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc.reshape(B, W, KV, Dh),
                   preferred_element_type=jnp.float32) / np.sqrt(Dh) + bias
    probs = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, vc.reshape(B, W, KV, Dh),
                   preferred_element_type=jnp.float32)
    o = o.reshape(q.shape).astype(vc.dtype)
    return h + jnp.einsum("bshk,hkd->bsd", o, p["wo"]), kc, vc


def _oracle_decode_step(cfg, params, cache, tokens, pos, ctx_len):
    """Write-then-attend decode, layer by layer in Python (dense, hybrid
    and audio families)."""
    W = min(cfg.sliding_window or ctx_len, ctx_len)
    pos = jnp.broadcast_to(pos, tokens.shape)
    window, slot = cfg.sliding_window, jnp.mod(pos, W)
    cache = jax.tree_util.tree_map(lambda a: a, cache)
    rows = jnp.arange(tokens.shape[0])
    cache["positions"] = cache["positions"].at[rows, slot].set(pos)
    cpos = cache["positions"]
    h = M._embed(cfg, params, tokens[:, None])
    ring = cache["attn"] if cfg.family == "hybrid" else cache["self"]
    ks, vs = list(ring["k"]), list(ring["v"])
    blocks = params["blocks"]
    for i in range(len(ks)):
        p = (params["shared_attn"] if cfg.family == "hybrid"
             else jax.tree_util.tree_map(lambda a: a[i], blocks["attn"]))
        h, ks[i], vs[i] = _oracle_attn(cfg, p, h, ks[i], vs[i], cpos, pos,
                                       window, slot)
        if cfg.family == "hybrid":
            h = M._apply_mlp(cfg, params["shared_mlp"], h)
            per = cfg.attn_every
            for j in range(i * per, (i + 1) * per):
                pl = jax.tree_util.tree_map(lambda a: a[j], blocks["mamba"])
                h, cs, ss = M._decode_mamba(cfg, pl, h, cache["ssm"]["conv"][j],
                                            cache["ssm"]["ssd"][j])
                cache["ssm"]["conv"] = cache["ssm"]["conv"].at[j].set(cs)
                cache["ssm"]["ssd"] = cache["ssm"]["ssd"].at[j].set(ss)
            continue
        pb = jax.tree_util.tree_map(lambda a: a[i], blocks)
        if cfg.family == "audio":
            h = M._decode_cross(cfg, pb["cross"], h, cache["cross_k"][i],
                                cache["cross_v"][i])
        h = M._apply_mlp(cfg, pb["mlp"], h)
    ring.update(k=jnp.stack(ks), v=jnp.stack(vs))
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return M._unembed(cfg, params, h)[:, 0], cache


def _stack_rows(rows):
    """Batch-1 prefill caches stacked into one cache of len(rows) rows: the
    positions buffer is (batch, W), every other leaf (layers, batch, ...)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, *a: jnp.concatenate(
            a, axis=0 if path[0].key == "positions" else 1), *rows)


def _prefilled(cfg, params, prompt_lens, ctx, ragged):
    """A cache after prefill: one batch of equal prompts for a scalar
    ``pos``, or per-row prefills of different lengths stacked for per-row
    ``pos``."""
    from repro.models import prefill
    B = len(prompt_lens)
    batch = _batch(cfg, B=B, S=max(prompt_lens))
    if not ragged:
        S = prompt_lens[0]
        batch = {k: (v[:, :S] if k == "tokens" else v) for k, v in batch.items()}
        return prefill(cfg, params, batch, ctx_len=ctx)[1]
    return _stack_rows([
        prefill(cfg, params, {"tokens": batch["tokens"][b:b + 1, :n]},
                ctx_len=ctx)[1] for b, n in enumerate(prompt_lens)])


@pytest.mark.parametrize("name,ragged,window", [
    ("qwen2-0.5b", False, None),
    ("qwen2-0.5b", True, None),
    ("qwen3-8b", False, 4),
    ("qwen3-8b", True, 4),
    ("zamba2-7b", False, None),
    ("seamless-m4t-large-v2", False, None),
])
def test_decode_step_matches_write_then_attend(name, ragged, window):
    """The step attends over the ring as it stands plus its own k/v and
    writes the new rows after the layer loop; each step gives the logits
    of writing first and attending over the whole ring, and the same
    cache: the written rows, and every other ring row untouched.  With a
    4-row window the ring wraps, so the row being overwritten (an old
    position) must stay out of the softmax.  ``ragged`` passes per-row
    ``pos`` (rows at different positions); otherwise one scalar ``pos``."""
    cfg = get_arch(name).reduced().with_(remat="none", sliding_window=window,
                                         dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ctx, B = 16, 2
    lens = [5, 7] if ragged else [6, 6]
    cache = _prefilled(cfg, params, lens, ctx, ragged)
    pos = np.asarray(lens, np.int32) if ragged else np.int32(lens[0])
    ring_key = "attn" if cfg.family == "hybrid" else "self"
    W = cache[ring_key]["k"].shape[2]
    new = jax.jit(lambda c, t, p: decode_step(cfg, params, c, t, p, ctx))
    old = jax.jit(lambda c, t, p: _oracle_decode_step(cfg, params, c, t, p,
                                                      ctx))
    toks = jax.random.randint(jax.random.PRNGKey(5), (8, B), 0, cfg.vocab)
    for t in range(8):
        lg, got = new(cache, toks[t], jnp.asarray(pos))
        lg_ref, want = old(cache, toks[t], jnp.asarray(pos))
        np.testing.assert_allclose(lg, lg_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got["positions"], want["positions"])
        written = np.zeros((B, W), bool)
        written[np.arange(B), np.asarray(pos) % W] = True
        for kv in ("k", "v"):
            g, w = np.asarray(got[ring_key][kv]), np.asarray(want[ring_key][kv])
            before = np.asarray(cache[ring_key][kv])
            np.testing.assert_array_equal(g[:, ~written], before[:, ~written])
            np.testing.assert_array_equal(g[0], w[0])   # layer 0: same inputs
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        for path, leaf in jax.tree_util.tree_leaves_with_path(got):
            if path[0].key not in (ring_key, "positions"):
                ref = want
                for p in path:
                    ref = ref[p.key]
                np.testing.assert_allclose(leaf, ref, rtol=1e-5, atol=1e-5)
        cache, pos = got, pos + 1
    if window is not None:        # the ring wrapped before the first step
        assert min(lens) > W


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_step_scalar_pos_is_per_row_pos_broadcast(name):
    """A scalar ``pos`` is the same step as that position given to every
    row: the same logits and the same cache, bit for bit."""
    from repro.models import prefill
    cfg = get_arch(name).reduced().with_(remat="none")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, ctx = 2, 16
    batch = _batch(cfg, B=B, S=8)
    _, cache = prefill(cfg, params, batch, ctx_len=ctx)
    step = jax.jit(lambda c, t, p: decode_step(cfg, params, c, t, p, ctx))
    tok = jnp.array([3, 5], jnp.int32)
    pos = batch["tokens"].shape[1]
    a_cache = b_cache = cache
    for t in range(2):
        a, a_cache = step(a_cache, tok, jnp.int32(pos + t))
        b, b_cache = step(b_cache, tok, jnp.full((B,), pos + t, jnp.int32))
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        for x, y in zip(jax.tree_util.tree_leaves(a_cache),
                        jax.tree_util.tree_leaves(b_cache)):
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))
    if "positions" in cache:
        assert cache["positions"].shape == (B, min(cfg.sliding_window or ctx,
                                                   ctx))
