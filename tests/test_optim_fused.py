"""Parity suite: fused Pallas optimizer path ≡ reference path (tier-1).

Everything runs ``update_impl="pallas_interpret"`` so it gates on CPU CI;
the compiled ``"pallas"`` impl is the same kernels minus the interpreter.

Exactness contract, checked leaf-by-leaf:

* step counts, clip norms and the gbuf swap: **bitwise identical**.
* f32 params / moments: a few ulp (rtol 1e-5 with a tiny atol for
  cancellation near zero) — the kernel body is op-identical to the
  reference, but XLA contracts its multiply-adds (m, v updates; the final
  ``p − lr·step``) into FMAs, one rounding where the eager reference takes
  two.  Only same-arithmetic survives this bound: a transposed operand,
  wrong bias correction or dropped clip factor fails by orders of
  magnitude.
* bf16 params: tolerance (the reference rounds the STEP to bf16 before
  subtracting; the kernel subtracts in f32 and rounds once).

Shapes deliberately exercise the ``_pad_to_tiles`` edge: sizes that are not
a multiple of block_rows·128, multi-dim leaves, and scalar () leaves.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.optim import (OptConfig, adam_init, fused_delayed_apply,
                         make_delayed_apply, make_optimizer,
                         reference_delayed_apply, resolve_update_impl)

F32 = jnp.float32


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _tree(dtype=jnp.float32, seed=0):
    """Pytree with padding-edge sizes: odd flat sizes, 2-D, and a scalar."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "w": jax.random.normal(ks[0], (33, 7), F32).astype(dtype),
        "b": jax.random.normal(ks[1], (5,), F32).astype(dtype),
        "scalar": jnp.asarray(0.37, dtype),
        "big": jax.random.normal(ks[2], (1000,), F32).astype(dtype),
    }


def _grads_like(params, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(params))
    return {k: (jax.random.normal(kk, p.shape, F32).astype(p.dtype)
                if p.ndim else jnp.asarray(0.1 * (seed + 1), p.dtype))
            for kk, (k, p) in zip(ks, sorted(params.items()))}


def _pair(name="adam", dtype=jnp.float32, **kw):
    cfg_ref = OptConfig(name=name, lr=1e-2, update_impl="reference", **kw)
    cfg_fused = OptConfig(name=name, lr=1e-2,
                          update_impl="pallas_interpret", **kw)
    return cfg_ref, cfg_fused


def _assert_state_close(sr, sf, dtype=jnp.float32):
    """count bitwise; f32 moments within FMA-contraction rounding.  With
    bf16 grads the reference round-trips the CLIPPED grad through bf16
    before the moment update (the kernel keeps it f32), so moments carry
    bf16-resolution differences."""
    np.testing.assert_array_equal(np.asarray(sr["count"]),
                                  np.asarray(sf["count"]))
    tol = dict(rtol=1e-5, atol=1e-8) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=5e-5)
    for key in ("m", "v"):
        for a, b in zip(jax.tree_util.tree_leaves(sr[key]),
                        jax.tree_util.tree_leaves(sf[key])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


def _assert_params(pr, pf, dtype):
    for k in pr:
        a, b = np.asarray(pr[k], np.float32), np.asarray(pf[k], np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-7)
        else:
            np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# resolve / config plumbing
# ---------------------------------------------------------------------------
def test_resolve_update_impl_falls_back_off_tpu():
    assert resolve_update_impl("reference") == "reference"
    assert resolve_update_impl("pallas_interpret") == "pallas_interpret"
    want = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    assert resolve_update_impl("pallas") == want
    with pytest.raises(ValueError, match="update_impl"):
        resolve_update_impl("cuda")


@pytest.mark.parametrize("impl", ["pallas", "pallas_pooled"])
def test_resolve_update_impl_keeps_compiled_on_tpu(monkeypatch, impl):
    """On a TPU backend a compiled request never resolves to the
    interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_update_impl(impl) == impl


def test_make_optimizer_rejects_unknown_impl():
    with pytest.raises(ValueError):
        make_optimizer(OptConfig(update_impl="fast"))


# ---------------------------------------------------------------------------
# plain (non-delayed) update parity over multi-step trajectories
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", ["adam", "sgd"])
def test_update_parity_multistep(name, dtype):
    cfg_ref, cfg_fused = _pair(name, dtype, clip_norm=1.0)
    init_r, upd_r = make_optimizer(cfg_ref)
    init_f, upd_f = make_optimizer(cfg_fused)
    pr = pf = _tree(dtype)
    sr, sf = init_r(pr), init_f(pf)
    for step in range(4):
        g = _grads_like(pr, step)
        pr, sr, gn_r = upd_r(g, sr, pr, cfg_ref, lr_scale=0.5)
        pf, sf, gn_f = upd_f(g, sf, pf, cfg_fused, lr_scale=0.5)
        np.testing.assert_array_equal(np.asarray(gn_r), np.asarray(gn_f))
    _assert_state_close(sr, sf, dtype)
    _assert_params(pr, pf, dtype)


def test_adam_weight_decay_and_no_clip_parity():
    cfg_ref, cfg_fused = _pair("adam", clip_norm=None, weight_decay=0.01)
    init_r, upd_r = make_optimizer(cfg_ref)
    _, upd_f = make_optimizer(cfg_fused)
    pr = pf = _tree()
    sr = sf = init_r(pr)
    g = _grads_like(pr, 3)
    pr, sr, _ = upd_r(g, sr, pr, cfg_ref)
    pf, sf, _ = upd_f(g, sf, pf, cfg_fused)
    _assert_state_close(sr, sf)
    _assert_params(pr, pf, jnp.float32)


def test_sgd_momentum_fused_parity():
    """Momentum-SGD runs the fused ``sgd_momentum_step`` kernel (m-buffer in
    the same HBM pass): trajectory tracks the reference within FMA rounding,
    m buffers included."""
    cfg_ref, cfg_fused = _pair("sgd", momentum=0.9, clip_norm=1.0)
    init_r, upd_r = make_optimizer(cfg_ref)
    _, upd_f = make_optimizer(cfg_fused)
    pr = pf = _tree()
    sr, sf = init_r(pr), init_r(pf)
    for step in range(3):
        g = _grads_like(pr, step)
        pr, sr, gn_r = upd_r(g, sr, pr, cfg_ref, lr_scale=0.5)
        pf, sf, gn_f = upd_f(g, sf, pf, cfg_fused, lr_scale=0.5)
        np.testing.assert_array_equal(np.asarray(gn_r), np.asarray(gn_f))
    _assert_state_close(sr, sf)
    _assert_params(pr, pf, jnp.float32)


def test_sgd_momentum_delayed_fused_parity():
    """Delayed momentum-SGD: one kernel consumes the stale buffer, updates
    the m-buffer, steps params AND swaps in the fresh grads (the last
    reference-fallback in ``fused_delayed_apply`` is gone)."""
    cfg_ref, cfg_fused = _pair("sgd", momentum=0.9, clip_norm=1.0)
    apply_r = make_delayed_apply(cfg_ref)
    apply_f = make_delayed_apply(cfg_fused)
    init, _ = make_optimizer(cfg_ref)
    pr = pf = _tree()
    sr, sf = init(pr), init(pf)
    br = bf = jax.tree_util.tree_map(jnp.zeros_like, pr)
    for step in range(4):
        g = _grads_like(pr, step)
        pr, br, sr, gn_r = apply_r(g, br, sr, pr, cfg_ref, lr_scale=0.25)
        pf, bf, sf, gn_f = apply_f(g, bf, sf, pf, cfg_fused, lr_scale=0.25)
        np.testing.assert_array_equal(np.asarray(gn_r), np.asarray(gn_f))
        for k in g:   # buffer swap is a pure copy: bitwise
            np.testing.assert_array_equal(np.asarray(bf[k]), np.asarray(g[k]))
    _assert_state_close(sr, sf)
    _assert_params(pr, pf, jnp.float32)


# ---------------------------------------------------------------------------
# delayed-buffer apply parity (the trainer's delay_rounds > 0 hot path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("delay_scale", [1.0, 1.0 / (1.0 + 3.0)])
@pytest.mark.parametrize("name", ["adam", "sgd"])
def test_delayed_apply_parity_multistep(name, delay_scale):
    """Fused apply consumes gbuf, steps params, buffers the fresh grads —
    trajectory must track the reference compose-and-swap leaf-by-leaf, for
    delay_scale ∈ {1, 1/(1+τ)}."""
    cfg_ref, cfg_fused = _pair(name, clip_norm=1.0)
    apply_r = make_delayed_apply(cfg_ref)
    apply_f = make_delayed_apply(cfg_fused)
    init, _ = make_optimizer(cfg_ref)
    pr = pf = _tree()
    sr, sf = init(pr), init(pf)
    br = bf = jax.tree_util.tree_map(jnp.zeros_like, pr)  # empty buffer
    for step in range(4):
        g = _grads_like(pr, step)
        pr, br, sr, gn_r = apply_r(g, br, sr, pr, cfg_ref,
                                   lr_scale=delay_scale)
        pf, bf, sf, gn_f = apply_f(g, bf, sf, pf, cfg_fused,
                                   lr_scale=delay_scale)
        np.testing.assert_array_equal(np.asarray(gn_r), np.asarray(gn_f))
        # the buffer swap is a pure copy: bitwise, and equal to the fresh g
        for k in g:
            np.testing.assert_array_equal(np.asarray(bf[k]), np.asarray(g[k]))
            np.testing.assert_array_equal(np.asarray(br[k]), np.asarray(bf[k]))
    _assert_state_close(sr, sf)
    _assert_params(pr, pf, jnp.float32)


def test_delayed_apply_first_step_empty_buffer_is_identity():
    """gate semantics: zero buffer + lr_scale 0 must leave params bitwise
    untouched on BOTH impls (trainer round 0)."""
    cfg_ref, cfg_fused = _pair("adam")
    init, _ = make_optimizer(cfg_ref)
    p = _tree()
    s = init(p)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
    g = _grads_like(p, 0)
    for cfg, apply in ((cfg_ref, reference_delayed_apply),
                       (cfg_fused, make_delayed_apply(cfg_fused))):
        newp, newb, news, _ = apply(g, zeros, s, p, cfg, lr_scale=0.0)
        for k in p:
            np.testing.assert_array_equal(np.asarray(newp[k]),
                                          np.asarray(p[k]))
            np.testing.assert_array_equal(np.asarray(newb[k]),
                                          np.asarray(g[k]))
        assert int(news["count"]) == 1


def test_fused_delayed_apply_under_jit():
    """The production call site is inside a jitted train step — the fused
    tree_map of pallas_calls must trace/compile cleanly."""
    cfg = OptConfig(name="adam", lr=1e-2, update_impl="pallas_interpret")
    init, _ = make_optimizer(cfg)
    p = _tree()
    s = init(p)
    b = jax.tree_util.tree_map(jnp.zeros_like, p)
    apply = make_delayed_apply(cfg)

    @jax.jit
    def step(p, b, s, g, scale):
        return apply(g, b, s, p, cfg, lr_scale=scale)

    g = _grads_like(p, 1)
    p1, b1, s1, gn = step(p, b, s, g, jnp.float32(0.25))
    want_p, want_b, want_s, _ = fused_delayed_apply(
        g, b, s, p, cfg, lr_scale=0.25, interpret=True)
    for a, w in zip(jax.tree_util.tree_leaves((p1, b1, s1)),
                    jax.tree_util.tree_leaves((want_p, want_b, want_s))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# trainer-level: fused curves track reference on the tier-1 workload
# ---------------------------------------------------------------------------
def test_async_trainer_fused_matches_reference_curves():
    """Acceptance: AsyncTrainer(update_impl="pallas_interpret") reproduces
    the reference training curve within tolerance on the reduced tier-1
    arch, including the delayed buffer and the per-round delay_scale
    input."""
    from jax.sharding import Mesh
    from repro.configs import get_arch
    from repro.data import DataConfig, HeterogeneousTokenPipeline
    from repro.distributed import AsyncTrainer, AsyncConfig
    from repro.optim import OptConfig as OC

    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    pipe = HeterogeneousTokenPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=16, global_batch=4, n_groups=1))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    curves, finals = {}, {}
    for impl in ("reference", "pallas_interpret"):
        tr = AsyncTrainer(cfg, mesh,
                          opt=OC(lr=1e-2, clip_norm=1.0, update_impl=impl),
                          async_cfg=AsyncConfig(delay_rounds=1))
        assert tr.update_impl == impl
        state = tr.init_state(jax.random.PRNGKey(0))
        step = jax.jit(tr.train_step_fn())
        losses = []
        for i in range(5):
            scale = jnp.float32(1.0 if i % 2 == 0 else 0.5)  # delay_scale in
            state, m = step(state, batch, jnp.ones((tr.n_groups,)), scale)
            losses.append(float(m["loss"]))
        curves[impl] = losses
        finals[impl] = state
    np.testing.assert_allclose(curves["reference"],
                               curves["pallas_interpret"], rtol=5e-3)
    # params are bf16 in the reduced arch: per-ELEMENT drift after 5
    # chaotic steps is unbounded in principle (rounding feeds back through
    # the gradients), so the state check is per-leaf norms, the curve
    # check above is the tight elementwise one
    for a, b in zip(jax.tree_util.tree_leaves(finals["reference"]),
                    jax.tree_util.tree_leaves(finals["pallas_interpret"])):
        na = float(jnp.linalg.norm(jnp.ravel(a).astype(F32)))
        nb = float(jnp.linalg.norm(jnp.ravel(b).astype(F32)))
        np.testing.assert_allclose(na, nb, rtol=5e-2, atol=1e-4)


def test_async_config_update_impl_overrides_opt():
    from jax.sharding import Mesh
    from repro.configs import get_arch
    from repro.distributed import AsyncTrainer, AsyncConfig
    from repro.optim import OptConfig as OC

    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    tr = AsyncTrainer(cfg, mesh, opt=OC(update_impl="reference"),
                      async_cfg=AsyncConfig(delay_rounds=1,
                                            update_impl="pallas_interpret"))
    assert tr.update_impl == "pallas_interpret"
    assert tr.opt.update_impl == "pallas_interpret"
