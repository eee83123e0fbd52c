"""AsyncTrainer: the paper's technique at trainer level (CPU, 1-device mesh).

Semantics checks mirror the theory: delayed buffer = one-round staleness,
worker masks = assignment rule, sync mode = baseline.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.configs import get_arch
from repro.core import (TimingModel, build_schedule, round_masks,
                        make_scheduler, heterogeneous_speeds)
from repro.data import DataConfig, HeterogeneousTokenPipeline
from repro.distributed import AsyncTrainer, AsyncConfig
from repro.optim import OptConfig


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _trainer(delay=1, **kw):
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    return cfg, AsyncTrainer(cfg, _mesh(),
                             opt=OptConfig(lr=1e-2, clip_norm=1.0),
                             async_cfg=AsyncConfig(delay_rounds=delay, **kw))


def _batch(cfg, B=4, S=16, seed=0):
    pipe = HeterogeneousTokenPipeline(
        DataConfig(vocab=cfg.vocab, seq_len=S, global_batch=B, n_groups=1,
                   seed=seed))
    return {k: jnp.asarray(v) for k, v in pipe.batch(seed).items()}


def test_state_tree_matches_specs():
    cfg, tr = _trainer()
    state = tr.init_state(jax.random.PRNGKey(0))
    ab = tr.abstract_state()
    flat_s = jax.tree_util.tree_leaves(state)
    flat_a = jax.tree_util.tree_leaves(ab)
    assert len(flat_s) == len(flat_a)
    for s, a in zip(flat_s, flat_a):
        assert s.shape == a.shape and s.dtype == a.dtype


def test_loss_decreases_sync_and_async():
    for delay in (0, 1):
        cfg, tr = _trainer(delay=delay)
        state = tr.init_state(jax.random.PRNGKey(0))
        step = jax.jit(tr.train_step_fn())
        batch = _batch(cfg)
        mask = jnp.ones((tr.n_groups,))
        losses = []
        for i in range(12):
            state, m = step(state, batch, mask)
            losses.append(float(m["loss"]))
        # memorise one batch: loss must drop substantially
        assert losses[-1] < losses[1] * 0.9, (delay, losses)


def test_first_round_is_identity_with_delay():
    """With an empty buffer the first update must be a no-op on params."""
    cfg, tr = _trainer(delay=1)
    state = tr.init_state(jax.random.PRNGKey(0))
    step = jax.jit(tr.train_step_fn())
    p0 = jax.tree_util.tree_leaves(state["params"])
    state2, _ = step(state, _batch(cfg), jnp.ones((tr.n_groups,)))
    p1 = jax.tree_util.tree_leaves(state2["params"])
    for a, b in zip(p0, p1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # buffer now holds the gradient
    assert float(sum(jnp.abs(g.astype(jnp.float32)).sum()
                     for g in jax.tree_util.tree_leaves(state2["gbuf"]))) > 0


def test_delayed_buffer_shifts_updates_by_one_round():
    """Async(delay=1) applied gradients at step t+1 equal sync gradients the
    trainer computed at step t — run both side by side on identical batches
    with SGD (no momentum) and compare parameter trajectories."""
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    mesh = _mesh()
    opt = OptConfig(name="sgd", lr=1e-2, clip_norm=None, momentum=0.0)
    tr_async = AsyncTrainer(cfg, mesh, opt=opt, async_cfg=AsyncConfig(1))
    tr_sync = AsyncTrainer(cfg, mesh, opt=opt, async_cfg=AsyncConfig(0))
    sa = tr_async.init_state(jax.random.PRNGKey(0))
    ss = tr_sync.init_state(jax.random.PRNGKey(0))
    step_a = jax.jit(tr_async.train_step_fn())
    step_s = jax.jit(tr_sync.train_step_fn())
    mask = jnp.ones((1,))
    b0 = _batch(cfg, seed=0)
    # async step 1 on b0: params unchanged, buffer ← g(x0, b0)
    sa, _ = step_a(sa, b0, mask)
    # async step 2 on anything: applies g(x0, b0) → equals sync step on b0
    sa, _ = step_a(sa, _batch(cfg, seed=1), mask)
    ss, _ = step_s(ss, b0, mask)
    for a, b in zip(jax.tree_util.tree_leaves(sa["params"]),
                    jax.tree_util.tree_leaves(ss["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


def test_worker_mask_zero_gives_zero_gradient():
    cfg, tr = _trainer(delay=0)
    state = tr.init_state(jax.random.PRNGKey(0))
    step = jax.jit(tr.train_step_fn())
    state2, m = step(state, _batch(cfg), jnp.zeros((tr.n_groups,)))
    assert float(m["grad_norm"]) == pytest.approx(0.0, abs=1e-6)


def test_masks_from_real_schedulers_drive_training():
    """End-to-end: scheduler → engine → round masks → trainer steps."""
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    mesh = _mesh()
    n_groups = 4   # virtual groups (> mesh data size is fine: masks weight examples)
    tr = AsyncTrainer(cfg, mesh, opt=OptConfig(lr=5e-3),
                      async_cfg=AsyncConfig(delay_rounds=1))
    tr.n_groups = n_groups
    sched = make_scheduler("shuffled", n_groups, seed=0)
    tm = TimingModel(heterogeneous_speeds(n_groups), "poisson", seed=0)
    s = build_schedule(sched, tm, 16 * 1)
    masks = round_masks(s)
    state = tr.init_state(jax.random.PRNGKey(0))
    step = jax.jit(tr.train_step_fn())
    batch = _batch(cfg, B=8)
    losses = []
    for q in range(masks.shape[0]):
        state, m = step(state, batch, jnp.asarray(masks[q]))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[1]
    assert all(np.isfinite(losses))


def test_moe_arch_trains():
    cfg = get_arch("deepseek-moe-16b").reduced().with_(remat="none")
    tr = AsyncTrainer(cfg, _mesh(), opt=OptConfig(lr=1e-2),
                      async_cfg=AsyncConfig(delay_rounds=1))
    state = tr.init_state(jax.random.PRNGKey(0))
    step = jax.jit(tr.train_step_fn())
    batch = _batch(cfg)
    for i in range(6):
        state, m = step(state, batch, jnp.ones((tr.n_groups,)))
    assert np.isfinite(float(m["loss"]))
    assert float(m["aux"]) > 0


def _round_masks(scheduler: str, b: int, rounds: int, seed: int = 0):
    sched = make_scheduler(scheduler, 4, b=b, seed=seed) if b > 1 else \
        make_scheduler(scheduler, 4, seed=seed)
    tm = TimingModel(heterogeneous_speeds(4, slow_factor=6.0), "poisson",
                     seed=seed)
    return round_masks(build_schedule(sched, tm, rounds * b), rounds)


@pytest.mark.parametrize("arch,masks_of", [
    ("qwen2-0.5b", "shuffled"),
    ("mamba2-370m", "shuffled"),
    ("qwen2-0.5b", "waiting"),          # a worker returns twice: weight 2
    ("qwen2-0.5b", "elastic"),          # a round whose groups all dropped
])
def test_gathered_step_matches_full_batch(arch, masks_of):
    """Gathering the participating groups' rows is the same step as the
    full batch with the other rows weighted 0: loss, applied gradient norm
    and the whole state after 3 rounds, computed in float32."""
    from repro.runtime.plan import participating_groups

    cfg = get_arch(arch).reduced().with_(remat="none", dtype="float32")
    tr = AsyncTrainer(cfg, _mesh(), opt=OptConfig(lr=1e-2, clip_norm=1.0),
                      async_cfg=AsyncConfig(delay_rounds=1))
    tr.n_groups = 4
    if masks_of == "waiting":
        masks = _round_masks("fedbuff", 2, 12)
        last = int(np.argmax(masks.max(axis=1) == 2))
        assert masks[last].max() == 2 and last >= 2
        masks = masks[last - 2:last + 1]
    else:
        masks = _round_masks("shuffled", 1, 3)
        if masks_of == "elastic":
            masks[1] = 0
    groups = participating_groups(masks)
    assert groups is not None and groups.shape[1] < 4
    step = jax.jit(tr.train_step_fn())
    full = gathered = tr.init_state(jax.random.PRNGKey(0))
    for q in range(3):
        batch = _batch(cfg, B=8, seed=q)
        mask = jnp.asarray(masks[q])
        full, m_full = step(full, batch, mask)
        gathered, m_g = step(gathered, batch, mask,
                             groups=jnp.asarray(groups[q]))
        for k in ("loss", "ce", "grad_norm", "participation"):
            np.testing.assert_allclose(float(m_g[k]), float(m_full[k]),
                                       rtol=1e-5, atol=1e-7,
                                       err_msg=f"round {q} {k}")
    if masks_of == "elastic":
        assert float(m_full["grad_norm"]) == 0.0     # round 1 applied 0
    for a, b in zip(jax.tree_util.tree_leaves(gathered),
                    jax.tree_util.tree_leaves(full)):
        # params and the delayed buffer are bf16: a gradient's last f32
        # digit may round an element one bf16 ulp (2^-7) apart, and the
        # later rounds run from there; wrong rows would be O(1) apart
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a32 - b32) <= 2.0 ** -7 * np.linalg.norm(b32)
