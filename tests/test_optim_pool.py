"""Pooled-state fused update (repro.optim.pool) — single-device suite.

The pooled impl changes the optimizer-state MEMORY LAYOUT (per-dtype
(n_shards, rows, 128) pool buffers, built once) and the launch count (one
pallas_call per dtype pool instead of one per leaf); the numbers must not
change.  Parity bounds follow tests/test_optim_fused.py: pure copies and
counts bitwise, f32 math within FMA-contraction rounding, bf16 at bf16
resolution, pooled global norms allclose (different reduction order than
the per-leaf Python sum).

The multi-device (shard_map over ZeRO shards) half of the suite lives in
tests/test_pool_multidevice.py.
"""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.optim import (OptConfig, adam_init, build_layout, global_norm,
                         init_pools, make_delayed_apply, make_optimizer,
                         pool_tree, pooled_delayed_apply,
                         pooled_global_norm, pooled_update,
                         reference_delayed_apply, sgd_update, adam_update,
                         unpool_tree, resolve_update_impl)
from repro.optim import optimizers as _optimizers

F32 = jnp.float32


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _tree(seed=0):
    """Mixed-dtype pytree (two pool groups) with padding-edge sizes: odd
    flat sizes, 2-D, a scalar, and sizes not divisible by n_shards."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "w": jax.random.normal(ks[0], (33, 7), F32).astype(jnp.bfloat16),
        "b": jax.random.normal(ks[1], (5,), F32),
        "scalar": jnp.asarray(0.37, F32),
        "big": jax.random.normal(ks[2], (1000,), F32).astype(jnp.bfloat16),
        "f32w": jax.random.normal(ks[3], (17, 3), F32),
    }


def _grads_like(params, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(params))
    return {k: (jax.random.normal(kk, p.shape, F32).astype(p.dtype)
                if p.ndim else jnp.asarray(0.1 * (seed + 1), p.dtype))
            for kk, (k, p) in zip(ks, sorted(params.items()))}


def _pools_for(layout, params, delayed=True):
    return init_pools(layout, params, delayed=delayed)


def _assert_tree_close(ref_tree, got_tree, param_tree=None):
    """Tolerance keyed off the PARAM dtype: bf16 params make the reference
    round-trip the clipped grad through bf16 before the moment update (the
    kernels keep f32), so their f32 moments still differ at bf16
    resolution — see tests/test_optim_fused.py."""
    params = param_tree if param_tree is not None else ref_tree
    for k in ref_tree:
        a = np.asarray(ref_tree[k], np.float32)
        b = np.asarray(got_tree[k], np.float32)
        if jnp.asarray(params[k]).dtype == jnp.bfloat16:
            np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-7)


# ---------------------------------------------------------------------------
# layout / roundtrip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 3, 4])
def test_layout_roundtrip_bitwise(n_shards):
    tree = _tree()
    lay = build_layout(tree, n_shards)
    assert lay.n_pools == 2                     # bf16 + f32 groups
    assert lay.n_leaves == len(tree)
    pools = pool_tree(lay, tree)
    for dk, pool in pools.items():
        assert pool.shape == (n_shards, lay.cols[dk] // 128, 128)
        assert lay.cols[dk] % (256 * 128) == 0  # whole compiled-kernel tiles
        assert str(pool.dtype) == dk
    back = unpool_tree(lay, pools)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k], np.float32),
                                      np.asarray(back[k], np.float32))


def test_pool_f32_override_groups_by_param_dtype():
    """Moments pool in f32 but under their PARAM's group (aligned bands)."""
    tree = _tree()
    lay = build_layout(tree, 4)
    m = jax.tree_util.tree_map(lambda p: jnp.ones(p.shape, F32), tree)
    pools = pool_tree(lay, m, dtype=F32)
    assert set(pools) == set(lay.groups)
    for dk, pool in pools.items():
        assert pool.dtype == F32
        assert pool.shape == (4, lay.cols[dk] // 128, 128)


def test_pooled_global_norm_matches_tree_norm():
    """Pad columns are zero ⇒ the single fused reduction per pool is the
    exact global norm (allclose: different summation order)."""
    tree = _tree()
    for n in (1, 4):
        lay = build_layout(tree, n)
        pools = pool_tree(lay, tree)
        np.testing.assert_allclose(float(pooled_global_norm(pools)),
                                   float(global_norm(tree)), rtol=1e-6)


def test_pool_tree_wrong_tree_raises():
    lay = build_layout(_tree(), 2)
    with pytest.raises(ValueError, match="leaves"):
        pool_tree(lay, {"just_one": jnp.zeros((3,))})


def test_layout_is_o_dtypes_not_o_leaves():
    """The launch-count claim: one kernel per dtype pool, however many
    leaves — here 5 leaves collapse into 2 pools."""
    lay = build_layout(_tree(), 2)
    assert lay.n_leaves == 5
    assert lay.n_pools == 2


# ---------------------------------------------------------------------------
# pooled update parity (single shard, no mesh)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("delay_scale", [1.0, 1.0 / (1.0 + 3.0)])
@pytest.mark.parametrize("name,momentum", [("adam", 0.0), ("sgd", 0.0),
                                           ("sgd", 0.9)])
def test_pooled_delayed_apply_parity_multistep(name, momentum, delay_scale):
    """Pooled delayed apply ≡ reference compose-and-swap over a 4-step
    trajectory, for Adam, SGD and momentum-SGD, on ZeRO-chunked (n_shards=4)
    pools."""
    cfg = OptConfig(name=name, lr=1e-2, momentum=momentum, clip_norm=1.0)
    tree = _tree()
    lay = build_layout(tree, 4)
    p_ref, s_ref = tree, adam_init(tree)
    b_ref = jax.tree_util.tree_map(jnp.zeros_like, tree)
    pools = _pools_for(lay, tree)
    count = jnp.zeros((), jnp.int32)
    for step in range(4):
        g = _grads_like(p_ref, step)
        p_ref, b_ref, s_ref, gn_r = reference_delayed_apply(
            g, b_ref, s_ref, p_ref, cfg, lr_scale=delay_scale)
        pools, count, gn_p = pooled_delayed_apply(
            pool_tree(lay, g), pools, count, cfg, lr_scale=delay_scale)
        np.testing.assert_allclose(float(gn_r), float(gn_p), rtol=1e-6)
        # the fresh-grads swap is a pure copy: bitwise through the pool
        got_b = unpool_tree(lay, {dk: b["gbuf"] for dk, b in pools.items()})
        for k in g:
            np.testing.assert_array_equal(np.asarray(got_b[k]),
                                          np.asarray(g[k]))
    assert int(count) == int(s_ref["count"])
    _assert_tree_close(p_ref,
                       unpool_tree(lay, {dk: b["p"]
                                         for dk, b in pools.items()}))
    _assert_tree_close(s_ref["m"],
                       unpool_tree(lay, {dk: b["m"]
                                         for dk, b in pools.items()}),
                       param_tree=p_ref)
    if name == "adam":
        _assert_tree_close(s_ref["v"],
                           unpool_tree(lay, {dk: b["v"]
                                             for dk, b in pools.items()}),
                           param_tree=p_ref)


@pytest.mark.parametrize("name,momentum", [("adam", 0.0), ("sgd", 0.0),
                                           ("sgd", 0.9)])
def test_pooled_update_parity_sync(name, momentum):
    """delay_rounds == 0: pooled_update ≡ the tree update (no gbuf)."""
    cfg = OptConfig(name=name, lr=1e-2, momentum=momentum, clip_norm=1.0)
    update = adam_update if name == "adam" else sgd_update
    tree = _tree()
    lay = build_layout(tree, 3)
    p_ref, s_ref = tree, adam_init(tree)
    pools = _pools_for(lay, tree, delayed=False)
    count = jnp.zeros((), jnp.int32)
    for step in range(3):
        g = _grads_like(p_ref, step)
        p_ref, s_ref, gn_r = update(g, s_ref, p_ref, cfg, lr_scale=0.5)
        pools, count, gn_p = pooled_update(
            pool_tree(lay, g), pools, count, cfg, lr_scale=0.5)
        np.testing.assert_allclose(float(gn_r), float(gn_p), rtol=1e-6)
    assert int(count) == int(s_ref["count"])
    _assert_tree_close(p_ref,
                       unpool_tree(lay, {dk: b["p"]
                                         for dk, b in pools.items()}))


def test_pooled_first_round_gate_is_identity():
    """zero buffer + lr_scale 0 leaves the params pool bitwise untouched
    and still buffers the fresh grads (trainer round 0)."""
    cfg = OptConfig(name="adam", lr=1e-2, clip_norm=1.0)
    tree = _tree()
    lay = build_layout(tree, 2)
    pools = _pools_for(lay, tree)
    g = _grads_like(tree, 0)
    new_pools, count, _ = pooled_delayed_apply(
        pool_tree(lay, g), pools, jnp.zeros((), jnp.int32), cfg, lr_scale=0.0)
    for dk in pools:
        np.testing.assert_array_equal(np.asarray(new_pools[dk]["p"]),
                                      np.asarray(pools[dk]["p"]))
    got_b = unpool_tree(lay, {dk: b["gbuf"] for dk, b in new_pools.items()})
    for k in g:
        np.testing.assert_array_equal(np.asarray(got_b[k]), np.asarray(g[k]))
    assert int(count) == 1


def test_pooled_apply_under_jit():
    """Production call site is a jitted train step: the pooled apply (pool
    the grads, one kernel per dtype) must trace/compile cleanly."""
    cfg = OptConfig(name="adam", lr=1e-2, clip_norm=1.0)
    tree = _tree()
    lay = build_layout(tree, 2)
    pools = _pools_for(lay, tree)

    @jax.jit
    def step(pools, g_pools, count, scale):
        return pooled_delayed_apply(g_pools, pools, count, cfg,
                                    lr_scale=scale)

    g = _grads_like(tree, 1)
    new_pools, count, gnorm = step(pools, pool_tree(lay, g),
                                   jnp.zeros((), jnp.int32),
                                   jnp.float32(0.25))
    want_pools, want_count, want_gn = pooled_delayed_apply(
        pool_tree(lay, g), pools, jnp.zeros((), jnp.int32), cfg,
        lr_scale=0.25)
    np.testing.assert_allclose(float(gnorm), float(want_gn), rtol=1e-6)
    for a, w in zip(jax.tree_util.tree_leaves(new_pools),
                    jax.tree_util.tree_leaves(want_pools)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# impl plumbing
# ---------------------------------------------------------------------------
def test_make_optimizer_rejects_pooled_impls():
    """Pooled impls change the state layout: the tree-based factories must
    refuse them loudly, not silently produce the wrong contract."""
    with pytest.raises(ValueError, match="pool"):
        make_optimizer(OptConfig(update_impl="pallas_pooled_interpret"))
    with pytest.raises(ValueError, match="pool"):
        make_delayed_apply(OptConfig(update_impl="pallas_pooled_interpret"))


def test_resolve_degrade_warns_once():
    """Off-TPU, "pallas"/"pallas_pooled" degrade to interpret with a
    ONE-TIME RuntimeWarning (silent interpreter-speed runs are a perf
    footgun); "*_interpret" requests stay silent."""
    if jax.default_backend() == "tpu":
        pytest.skip("degradation only happens off-TPU")
    _optimizers._degrade_warned.clear()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_update_impl("pallas_pooled") \
            == "pallas_pooled_interpret"
        assert resolve_update_impl("pallas_pooled") \
            == "pallas_pooled_interpret"   # second call: no new warning
        assert resolve_update_impl("pallas_pooled_interpret") \
            == "pallas_pooled_interpret"
        assert resolve_update_impl("reference") == "reference"
    ours = [w for w in caught if issubclass(w.category, RuntimeWarning)
            and "pallas_pooled" in str(w.message)]
    assert len(ours) == 1
    assert "interpret" in str(ours[0].message).lower()
    _optimizers._degrade_warned.clear()


# ---------------------------------------------------------------------------
# trainer-level: pooled state end-to-end on the tier-1 workload
# ---------------------------------------------------------------------------
def _trainer_pieces(impl, delay_rounds=1):
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
    tr = AsyncTrainer(cfg, mesh,
                      opt=OC(lr=1e-2, clip_norm=1.0, update_impl=impl),
                      async_cfg=AsyncConfig(delay_rounds=delay_rounds))
    return tr, batch


def test_async_trainer_pooled_state_structure():
    tr, _ = _trainer_pieces("pallas_pooled_interpret")
    assert tr.pooled and tr.update_impl == "pallas_pooled_interpret"
    lay = tr.pool_layout
    assert lay.n_shards == 1                 # 1-device mesh: one ZeRO shard
    state = tr.init_state(jax.random.PRNGKey(0))
    assert set(state) == {"pools", "opt", "step"}
    for dk, grp in state["pools"].items():
        assert set(grp) == {"p", "m", "v", "gbuf"}
        assert grp["p"].shape == (lay.n_shards, lay.cols[dk] // 128, 128)
        assert grp["m"].dtype == jnp.float32
    # abstract/sharding trees mirror the concrete state
    ab = tr.abstract_state()
    assert jax.tree_util.tree_structure(ab) \
        == jax.tree_util.tree_structure(state)
    sh = tr.state_shardings()
    assert jax.tree_util.tree_structure(sh) \
        == jax.tree_util.tree_structure(state)
    # params_of unpools back to the init tree bitwise
    from repro.models import model as M
    want = M.init_params(tr.cfg, jax.random.PRNGKey(0))
    got = tr.params_of(state)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_async_trainer_pooled_matches_reference_curves():
    """Acceptance: AsyncTrainer(update_impl="pallas_pooled_interpret")
    reproduces the reference training curve within the documented
    tolerances, including the delayed buffer and per-round delay_scale."""
    curves, finals = {}, {}
    for impl in ("reference", "pallas_pooled_interpret"):
        tr, batch = _trainer_pieces(impl)
        state = tr.init_state(jax.random.PRNGKey(0))
        step = jax.jit(tr.train_step_fn())
        losses = []
        for i in range(5):
            scale = jnp.float32(1.0 if i % 2 == 0 else 0.5)
            state, m = step(state, batch, jnp.ones((tr.n_groups,)), scale)
            losses.append(float(m["loss"]))
        curves[impl] = losses
        finals[impl] = tr.params_of(state)
    np.testing.assert_allclose(curves["reference"],
                               curves["pallas_pooled_interpret"], rtol=5e-3)
    # bf16 per-element drift is chaotic over 5 steps: compare leaf norms
    for a, b in zip(jax.tree_util.tree_leaves(finals["reference"]),
                    jax.tree_util.tree_leaves(
                        finals["pallas_pooled_interpret"])):
        na = float(jnp.linalg.norm(jnp.ravel(a).astype(F32)))
        nb = float(jnp.linalg.norm(jnp.ravel(b).astype(F32)))
        np.testing.assert_allclose(na, nb, rtol=5e-2, atol=1e-4)


def test_async_trainer_pooled_sync_baseline():
    """delay_rounds == 0 (synchronous SGD baseline) through the pooled
    update: no gbuf pool in the state, curves track reference."""
    curves = {}
    for impl in ("reference", "pallas_pooled_interpret"):
        tr, batch = _trainer_pieces(impl, delay_rounds=0)
        state = tr.init_state(jax.random.PRNGKey(0))
        if impl.startswith("pallas_pooled"):
            for grp in state["pools"].values():
                assert "gbuf" not in grp
        step = jax.jit(tr.train_step_fn())
        losses = []
        for _ in range(3):
            state, m = step(state, batch, jnp.ones((tr.n_groups,)))
            losses.append(float(m["loss"]))
        curves[impl] = losses
    np.testing.assert_allclose(curves["reference"],
                               curves["pallas_pooled_interpret"], rtol=5e-3)
