"""Golden parity suite for the ``repro.runtime`` whole-run executor.

The load-bearing guarantee: the scan executor is the SAME run as the eager
per-round loop — same plan, same device-synthesised batches, same step
function — only the dispatch differs.  Curves must therefore agree within
the documented FMA-contraction tolerances (tests/test_optim_fused.py:
XLA may contract multiply-adds differently when the step is compiled
inside a ``lax.scan`` body than when compiled standalone; bitwise f32
equality is NOT attainable, rtol=1e-5 + small atol is the contract).

Covered here:

* plan lowering (masks/scales/keys shapes, resume-stable key folding,
  γ-axis grid_scales),
* scan-vs-eager curve parity across (scheduler × update_impl ×
  delay-adaptive) combos, including the sync (delay_rounds=0) baseline,
* the metric transports: per-chunk readback, overlapped deferred readback,
  the per-round io_callback tap, and metric-free execution — all the same
  curves, with honest ExecStats (launches / host_syncs / tap_events),
* the vmapped γ-grid lane: ``run_grid[i]`` ≡ a single-γ scan run on a
  trainer built at γ_i,
* the ``groups`` channel: read off the masks, counted in
  ``rows_computed``, and absent from the program wherever the step keeps
  the whole batch,
* chunk-boundary edge cases: ``rounds_per_launch`` of 1, ``rounds``, and a
  ragged ``rounds % K != 0`` split, plus ``on_step`` barrier semantics,
* checkpoint-resume at a chunk boundary (pooled state) ≡ uninterrupted,
* ``TrainerBackend`` wiring (spec/constructor runtime+metrics resolution,
  the grid lane end-to-end vs the sequential oracle), and
* an 8-virtual-device pooled ZeRO-sharded scan run (subprocess
  self-bootstrap on single-device hosts, mirroring
  tests/test_pool_multidevice.py).
"""
import dataclasses
import hashlib
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import ExperimentSpec, RunResult, TrainJob, TrainerBackend
from repro.core import lower_rounds, round_delay_scales, round_masks
from repro.runtime import (METRICS, PlanExecutor, RunPlan, compile_plan,
                           execute, fold_data_keys, make_batch_fn,
                           run_eager, run_grid, run_scan)

MULTI = jax.device_count() >= 8

#: micro transformer: jit/compile dominates CPU test wall time, so shrink
#: the per-step math to noise and spend the budget on dispatch coverage
MICRO = (("n_layers", 1), ("d_model", 64), ("n_heads", 2), ("n_kv_heads", 1),
         ("d_ff", 64), ("vocab", 97))

TOL = dict(rtol=1e-5, atol=1e-7)


def _job(**kw):
    kw.setdefault("arch", "qwen2-0.5b")
    kw.setdefault("global_batch", 8)
    kw.setdefault("seq_len", 16)
    kw.setdefault("arch_overrides", MICRO)
    return TrainJob(**kw)


def _spec(job, scheduler="shuffled", T=6, adaptive=False, **kw):
    kw.setdefault("stepsize",
                  f"delay_adaptive:{3e-3}" if adaptive else 3e-3)
    return ExperimentSpec(scheduler=scheduler, timing="poisson:slow=6",
                          objective=job, T=T, n_workers=4, seed=0, **kw)


def _trainer(job, mesh=None, lr=3e-3):
    from jax.sharding import Mesh
    from repro.distributed import AsyncTrainer, AsyncConfig
    from repro.optim import OptConfig

    if mesh is None:
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
    tr = AsyncTrainer(
        job.make_arch(), mesh,
        opt=OptConfig(lr=lr, clip_norm=job.clip_norm,
                      update_impl=job.update_impl),
        async_cfg=AsyncConfig(delay_rounds=job.delay_rounds))
    tr.n_groups = 4
    return tr


def _plan_for(spec, job):
    _, schedule = TrainerBackend.masks_for(spec, 4)
    return compile_plan(schedule, job, rounds=spec.T, n_groups=4,
                        seed=spec.seed,
                        adaptive=spec.stepsize.kind == "delay_adaptive")


@pytest.mark.skipif(MULTI, reason="already on a multi-device host")
def test_multidevice_suite_in_subprocess():
    """Single-device hosts: run this file under 8 virtual CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__), "-k", "multidevice"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"8-device runtime suite failed:\n{r.stdout}\n{r.stderr}"
    assert " passed" in r.stdout


# ---------------------------------------------------------------------------
# plan lowering
# ---------------------------------------------------------------------------
def test_lower_rounds_matches_components():
    spec = _spec(_job(), scheduler="fedbuff:b=2", T=10)
    _, schedule = TrainerBackend.masks_for(spec, 4)
    masks, ones = lower_rounds(schedule, 10)
    np.testing.assert_array_equal(masks, round_masks(schedule, 10))
    np.testing.assert_array_equal(ones, np.ones(10, np.float32))
    m2, scales = lower_rounds(schedule, 10, delay_rounds=1, adaptive=True)
    np.testing.assert_array_equal(m2, masks)
    np.testing.assert_array_equal(
        scales, round_delay_scales(schedule, 10, delay_rounds=1))


def test_compile_plan_builds_arch_once():
    """Regression: with zipf_as set, compile_plan used to call
    job.make_arch() twice (once for the vocab probe, once for the
    pipeline config) — the probe must reuse the single build."""
    calls = []

    class CountingJob(TrainJob):
        def make_arch(self):
            calls.append(1)
            return super().make_arch()

    job = CountingJob(arch_overrides=MICRO)
    spec = _spec(job, T=5)
    _, schedule = TrainerBackend.masks_for(spec, 4)
    compile_plan(schedule, job, rounds=5, n_groups=4, seed=0,
                 zipf_as=np.full(5, 1.2))
    assert len(calls) == 1, f"make_arch called {len(calls)} times"


def test_compile_plan_shapes_and_validation():
    job = _job()
    spec = _spec(job, T=7)
    plan = _plan_for(spec, job)
    assert plan.rounds == 7 and plan.n_groups == 4
    assert plan.masks.shape == (7, 4)
    assert plan.delay_scales.shape == (7,)
    assert plan.data_keys.shape == (7, 2)
    assert plan.vocab == 97                      # MICRO override
    assert plan.group_perms.shape == (4, 97)
    assert np.all(np.diff(plan.token_cdf) >= 0)
    assert abs(plan.token_cdf[-1] - 1.0) < 1e-5
    # not adaptive → neutral scales
    np.testing.assert_array_equal(plan.delay_scales, np.ones(7, np.float32))
    with pytest.raises(ValueError, match="rounds"):
        RunPlan(masks=plan.masks, delay_scales=plan.delay_scales[:3],
                data_keys=plan.data_keys, token_cdf=plan.token_cdf,
                group_perms=plan.group_perms, global_batch=8, seq_len=16,
                seed=0)
    with pytest.raises(ValueError, match="divide"):
        RunPlan(masks=plan.masks, delay_scales=plan.delay_scales,
                data_keys=plan.data_keys, token_cdf=plan.token_cdf,
                group_perms=plan.group_perms, global_batch=9, seq_len=16,
                seed=0)


def test_fold_data_keys_resume_stable():
    """Key at round q must not depend on the horizon — that is what makes
    a resumed run regenerate the identical batch stream."""
    k10, k4 = fold_data_keys(3, 10), fold_data_keys(3, 4)
    np.testing.assert_array_equal(k10[:4], k4)
    assert not np.array_equal(fold_data_keys(4, 4), k4)      # seed matters
    assert len({tuple(k) for k in k10}) == 10                # distinct rounds


def test_device_batch_synthesis_is_grouped_and_deterministic():
    job = _job()
    plan = _plan_for(_spec(job, T=3), job)
    batch_of = make_batch_fn(plan, job.make_arch())
    b0 = batch_of(jnp.asarray(plan.data_keys[0]))
    b0b = batch_of(jnp.asarray(plan.data_keys[0]))
    b1 = batch_of(jnp.asarray(plan.data_keys[1]))
    toks = np.asarray(b0["tokens"])
    assert toks.shape == (8, 16) and toks.dtype == np.int32
    assert toks.min() >= 0 and toks.max() < plan.vocab
    np.testing.assert_array_equal(toks, np.asarray(b0b["tokens"]))
    assert not np.array_equal(toks, np.asarray(b1["tokens"]))


# ---------------------------------------------------------------------------
# golden scan-vs-eager parity (scheduler × update_impl × delay-adaptive)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheduler,impl,adaptive,delay_rounds", [
    ("shuffled", "reference", False, 1),
    ("fedbuff:b=2", "reference", True, 1),
    ("pure", "reference", False, 0),                  # sync baseline
    ("random", "pallas_interpret", False, 1),
    ("shuffled", "pallas_pooled_interpret", True, 1),
])
def test_scan_matches_eager(scheduler, impl, adaptive, delay_rounds):
    job = _job(update_impl=impl, delay_rounds=delay_rounds)
    spec = _spec(job, scheduler=scheduler, T=6, adaptive=adaptive)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    r_e = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    r_s = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                   rounds_per_launch=4)               # ragged: 4 + 2
    # honest accounting: eager = one STEP launch + one blocking metric
    # readback per round (the batch-synthesis jit is not a round launch);
    # scan without a callback overlaps chunks and reads back ONCE
    assert r_e.launches == 6 and r_e.host_syncs == 6
    assert r_e.tap_events == 0
    assert r_s.launches == 2 and r_s.host_syncs == 1
    assert r_s.tap_events == 0
    # both lanes computed only the participating groups' rows
    assert plan.groups is not None
    assert r_s.stats.rows_computed == r_e.stats.rows_computed \
        == 6 * plan.step_rows
    for k in METRICS:
        np.testing.assert_allclose(r_s.metrics[k], r_e.metrics[k], **TOL,
                                   err_msg=f"metric {k}")
    if adaptive:        # the adaptive lowering actually ran (the rule may
        assert plan.adaptive     # still saturate at 1 for short horizons)
        assert np.all(plan.delay_scales <= 1.0)


# ---------------------------------------------------------------------------
# rows computed: the plan's groups channel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheduler,rows", [
    ("shuffled", 2),            # one group of 2 rows a round
    ("minibatch:b=4", 8),       # every group takes part: the whole batch
])
def test_step_rows_are_read_off_the_masks_and_counted(scheduler, rows):
    from repro.obs import Recorder

    job = _job()
    plan = _plan_for(_spec(job, scheduler=scheduler, T=6), job)
    assert plan.summary()["step_rows"] == rows
    assert (plan.groups is None) == (rows == job.global_batch)
    if plan.groups is not None:
        # each round's participants are among its computed groups
        for q in range(plan.rounds):
            assert set(np.flatnonzero(plan.masks[q])) <= \
                set(plan.groups[q].tolist())
    tr = _trainer(job)
    rec = Recorder()
    r_s = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                   rounds_per_launch=4, recorder=rec)
    r_e = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    assert r_s.stats.rows_computed == r_e.stats.rows_computed \
        == plan.rounds * rows
    assert rec.tracer.counters()["rows_computed"] == plan.rounds * rows


def test_groups_channel_follows_the_masks():
    """The channel is read off the plan's masks, also when they are
    replaced: full participation drops it, a round with no participant
    pads with idle groups, and the width is the busiest round's."""
    job = _job()
    plan = _plan_for(_spec(job, T=4), job)
    assert plan.groups.shape == (4, 1)
    full = dataclasses.replace(plan, masks=np.ones((4, 4), np.float32))
    assert full.groups is None and full.step_rows == 8
    masks = np.zeros((4, 4), np.float32)
    masks[0, 3] = masks[2, [1, 2]] = 1.0
    masks[3, 0] = 2.0
    odd = dataclasses.replace(plan, masks=masks)
    np.testing.assert_array_equal(odd.groups,
                                  [[3, 0], [0, 1], [1, 2], [0, 1]])
    assert odd.step_rows == 4


def _chunk_hlo_hash(job, scheduler) -> str:
    plan = _plan_for(_spec(job, scheduler=scheduler, T=4), job)
    tr = _trainer(job)
    ex = PlanExecutor(tr, plan, donate=False)
    lowered = ex._chunk_jit("chunk").__wrapped_jit__.lower(
        tr.init_state(jax.random.PRNGKey(0)), ex._slices(0, plan.rounds))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize("arch,gathers", [
    ("qwen2-0.5b", True),
    ("deepseek-moe-16b", False),     # the aux loss covers every row
])
def test_full_batch_runs_lower_to_the_full_batch_program(arch, gathers):
    """Where the step keeps the whole batch, a shuffled plan lowers to the
    same chunk program, text for text, as a plan in which every group
    takes part (no groups channel); plans of one shape differ in data
    only."""
    job = _job(arch=arch, arch_overrides=MICRO if arch == "qwen2-0.5b"
               else ())
    assert (_chunk_hlo_hash(job, "shuffled")
            != _chunk_hlo_hash(job, "minibatch:b=4")) is gathers


# ---------------------------------------------------------------------------
# chunk-boundary edge cases + on_step barrier semantics
# ---------------------------------------------------------------------------
def test_chunk_boundary_edge_cases():
    """K=1 (degenerate eager), K=rounds (one launch), ragged K — all the
    same curves; on_step fires once per round, at chunk boundaries, in
    order.  With a callback the readback blocks every chunk (host_syncs
    == launches — the callback must see values)."""
    job = _job()
    spec = _spec(job, T=5)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    base = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    for k, launches in ((1, 5), (3, 2), (5, 1)):
        seen = []
        r = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                     rounds_per_launch=k,
                     on_step=lambda i, st, m: seen.append((i, m["loss"])))
        assert r.launches == launches and r.host_syncs == launches
        assert [i for i, _ in seen] == list(range(5))
        np.testing.assert_allclose([l for _, l in seen],
                                   base.metrics["loss"], **TOL)
        for name in METRICS:
            np.testing.assert_allclose(r.metrics[name], base.metrics[name],
                                       **TOL, err_msg=f"K={k} {name}")


# ---------------------------------------------------------------------------
# metric transports: tap / none / overlapped chunk
# ---------------------------------------------------------------------------
def test_metrics_tap_streams_per_round():
    """The io_callback tap delivers every round's metrics in order with
    ZERO blocking readbacks, fires on_step per round with state=None
    (mid-scan state never materialises on host), and the curves match the
    eager oracle — even at rounds_per_launch == rounds (one launch for
    the whole run, the configuration a chunk barrier would make
    log-silent)."""
    job = _job()
    spec = _spec(job, T=6)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    base = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    seen = []
    r = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                 rounds_per_launch=6, metrics="tap",
                 on_step=lambda i, st, m: seen.append((i, st, m["loss"])))
    assert r.launches == 1 and r.host_syncs == 0 and r.tap_events == 6
    assert [i for i, _, _ in seen] == list(range(6))
    assert all(st is None for _, st, _ in seen)
    np.testing.assert_allclose([l for _, _, l in seen],
                               base.metrics["loss"], **TOL)
    for k in METRICS:
        np.testing.assert_allclose(r.metrics[k], base.metrics[k], **TOL,
                                   err_msg=f"tap {k}")
    # ragged chunking under tap: same stream, one tap per round
    seen2 = []
    r2 = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                  rounds_per_launch=4, metrics="tap",
                  on_step=lambda i, st, m: seen2.append(i))
    assert r2.launches == 2 and r2.tap_events == 6
    assert seen2 == list(range(6))
    np.testing.assert_allclose(r2.metrics["loss"], base.metrics["loss"],
                               **TOL)


def test_tap_row_drop_fails_loudly():
    """A tap row that never reaches the host sink must abort the run with
    the delivered/expected accounting — never return silently truncated
    curves.  The chunk jit binds ``self._emit_tap`` at trace time, so the
    lossy transport is patched onto the instance BEFORE the first
    ``run_scan`` builds the program."""
    job = _job()
    spec = _spec(job, T=6)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    ex = PlanExecutor(tr, plan, donate=False)
    orig = ex._emit_tap

    def lossy(idx, row):
        if int(idx) == 2:
            return                    # swallow one io_callback delivery
        orig(idx, row)

    ex._emit_tap = lossy
    state = tr.init_state(jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="io_callback was dropped"):
        ex.run_scan(state, rounds_per_launch=3, metrics="tap")
    # the counts in the message are the delivered/expected pair
    with pytest.raises(RuntimeError, match=r"5/6"):
        ex.run_scan(tr.init_state(jax.random.PRNGKey(0)),
                    rounds_per_launch=3, metrics="tap")


def test_metrics_none_discards_on_device():
    """metrics="none": no curves, no syncs, no taps — and an on_step
    callback is rejected up front (it would silently never fire)."""
    job = _job()
    spec = _spec(job, T=4)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    base = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    r = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                 rounds_per_launch=2, metrics="none")
    assert r.metrics == {}
    assert r.launches == 2 and r.host_syncs == 0 and r.tap_events == 0
    # the run still trained: final params match the eager oracle's
    pe = tr.params_of(base.state)
    pn = tr.params_of(r.state)
    for a, b in zip(jax.tree_util.tree_leaves(pe),
                    jax.tree_util.tree_leaves(pn)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)
    with pytest.raises(ValueError, match="on_step"):
        run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                 metrics="none", on_step=lambda i, st, m: None)
    with pytest.raises(ValueError, match="unknown metrics"):
        run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                 metrics="streaming")


def test_neutral_plan_honors_trainer_static_delay_rule():
    """A NON-adaptive plan must not override the trainer's own static
    ``AsyncConfig(delay_adaptive=True)`` 1/(1+delay) rule with an explicit
    all-ones scale — the executor calls the 3-arg step, the trainer's
    config stays in charge, and scan still matches eager."""
    from jax.sharding import Mesh
    from repro.distributed import AsyncTrainer, AsyncConfig
    from repro.optim import OptConfig

    job = _job()
    spec = _spec(job, T=4)
    plan = _plan_for(spec, job)
    assert not plan.adaptive
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    tr_static = AsyncTrainer(
        job.make_arch(), mesh,
        opt=OptConfig(lr=3e-3, clip_norm=job.clip_norm),
        async_cfg=AsyncConfig(delay_rounds=1, delay_adaptive=True))
    tr_static.n_groups = 4
    r_e = run_eager(tr_static, plan,
                    tr_static.init_state(jax.random.PRNGKey(0)))
    r_s = run_scan(tr_static, plan,
                   tr_static.init_state(jax.random.PRNGKey(0)),
                   rounds_per_launch=2)
    for k in METRICS:
        np.testing.assert_allclose(r_s.metrics[k], r_e.metrics[k], **TOL)
    # and the halved stepsize actually bit: curves diverge from the plain
    # (delay_adaptive=False) trainer once the first buffered grad applies
    plain = run_eager(_trainer(job), plan,
                      tr_static.init_state(jax.random.PRNGKey(0)))
    assert not np.allclose(plain.metrics["loss"][2:],
                           r_e.metrics["loss"][2:], rtol=1e-6)


def test_execute_dispatch_and_unknown_runtime():
    job = _job()
    plan = _plan_for(_spec(job, T=2), job)
    tr = _trainer(job)
    r = execute(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                runtime="scan", rounds_per_launch=2)
    assert r.launches == 1
    r = execute(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                runtime="scan", rounds_per_launch=2, metrics="none")
    assert r.metrics == {}
    with pytest.raises(ValueError, match="unknown runtime"):
        execute(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                runtime="vectorized")


# ---------------------------------------------------------------------------
# vmapped γ-grid lane
# ---------------------------------------------------------------------------
#: exact-binary γ ratios so the lane's γ_g = γ_base·(γ_g/γ_base) product is
#: bitwise the single-run lr and the remaining diff is pure FMA noise
GRID_GAMMAS = (3e-3, 1.5e-3, 7.5e-4, 3.75e-4)


def _grid_plan_for(spec, job, gammas=GRID_GAMMAS):
    _, schedule = TrainerBackend.masks_for(spec, 4)
    return compile_plan(schedule, job, rounds=spec.T, n_groups=4,
                        seed=spec.seed, grid_gammas=gammas)


def test_grid_plan_lowering_and_validation():
    job = _job()
    spec = _spec(job, T=5)
    plan = _grid_plan_for(spec, job)
    assert plan.n_grid == 4
    assert plan.grid_scales.shape == (4, 5)
    # row g is γ_g/γ_0 × the (neutral) per-round scales
    np.testing.assert_allclose(
        plan.grid_scales,
        (np.asarray(GRID_GAMMAS, np.float32) / np.float32(3e-3))[:, None]
        * np.ones((1, 5), np.float32))
    assert plan.summary()["n_grid"] == 4
    single = _plan_for(spec, job)
    assert single.n_grid == 0
    with pytest.raises(ValueError, match="γ-axis"):
        single.grid_slice(0, 2)
    with pytest.raises(ValueError, match="grid_scales"):
        RunPlan(masks=plan.masks, delay_scales=plan.delay_scales,
                data_keys=plan.data_keys, token_cdf=plan.token_cdf,
                group_perms=plan.group_perms, global_batch=8, seq_len=16,
                seed=0, grid_scales=plan.grid_scales[:, :3])


def test_run_grid_matches_single_gamma_runs():
    """The load-bearing grid-lane gate: lane i of one vmapped grid run ≡
    a standalone scan run on a trainer built at lr=γ_i (same plan, same
    batches), within the documented FMA tolerances."""
    job = _job()
    spec = _spec(job, T=6)
    gplan = _grid_plan_for(spec, job)
    plan = _plan_for(spec, job)
    tr = _trainer(job)                    # lr = 3e-3 = γ_base
    rg = run_grid(tr, gplan, tr.init_state(jax.random.PRNGKey(0)),
                  rounds_per_launch=4)    # ragged: 4 + 2
    assert rg.metrics["loss"].shape == (4, 6)
    assert rg.launches == 2 and rg.host_syncs == 1
    assert gplan.groups is not None        # the lane gathers rows too
    assert rg.stats.rows_computed == 4 * 6 * gplan.step_rows
    # γ really differed across lanes
    assert not np.allclose(rg.metrics["loss"][0], rg.metrics["loss"][3],
                           rtol=1e-6)
    for i, g in enumerate(GRID_GAMMAS):
        tri = _trainer(_job(), lr=g)
        ri = run_scan(tri, plan, tri.init_state(jax.random.PRNGKey(0)),
                      rounds_per_launch=4)
        for k in METRICS:
            np.testing.assert_allclose(
                rg.metrics[k][i], ri.metrics[k], **TOL,
                err_msg=f"grid lane γ={g} metric {k}")
    # rows is a single-run view; grid curves must not silently flatten
    with pytest.raises(ValueError, match="grid"):
        rg.rows


def test_run_grid_stacked_resume_and_modes():
    """run_grid accepts an already-stacked state (resume), supports
    metrics="none", and rejects tap / plans without a γ-axis."""
    job = _job()
    spec = _spec(job, T=4)
    _, schedule = TrainerBackend.masks_for(spec, 4)
    gplan = _grid_plan_for(spec, job)
    # the same schedule truncated to its first 2 rounds — a run stopped
    # at the chunk boundary (plan prefixes are exact: lower_rounds slices
    # the same realisation, data keys are horizon-independent)
    head_plan = compile_plan(schedule, job, rounds=2, n_groups=4, seed=0,
                             grid_gammas=GRID_GAMMAS)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    ex = PlanExecutor(tr, gplan, donate=False)
    full = ex.run_grid(tr.init_state(jax.random.PRNGKey(0)),
                       rounds_per_launch=2)
    head = PlanExecutor(tr, head_plan, donate=False).run_grid(
        tr.init_state(jax.random.PRNGKey(0)), rounds_per_launch=2)
    # resume: feed the stacked carry back in at the boundary
    tail = ex.run_grid(head.state, rounds_per_launch=2, start_round=2)
    assert tail.metrics["loss"].shape == (4, 2)
    np.testing.assert_allclose(tail.metrics["loss"],
                               full.metrics["loss"][:, 2:], **TOL)
    r_none = ex.run_grid(tr.init_state(jax.random.PRNGKey(0)),
                         rounds_per_launch=4, metrics="none")
    assert r_none.metrics == {} and r_none.host_syncs == 0
    with pytest.raises(ValueError, match="tap"):
        ex.run_grid(tr.init_state(jax.random.PRNGKey(0)), metrics="tap")
    with pytest.raises(ValueError, match="γ-axis"):
        run_grid(tr, plan, tr.init_state(jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# checkpoint-resume parity at a chunk boundary (pooled state)
# ---------------------------------------------------------------------------
def test_checkpoint_resume_parity_pooled(tmp_path):
    """Save at a chunk boundary via repro.checkpoint, restore (pooled
    pools + scalars), finish — loss/grad-norm curves must match an
    uninterrupted run within the FMA tolerances."""
    from repro import checkpoint

    job = _job(update_impl="pallas_pooled_interpret")
    spec = _spec(job, T=6)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    assert tr.pooled

    full = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                    rounds_per_launch=3)

    ckpt = str(tmp_path / "ckpt")
    saved = {}

    def barrier(i, state, m):
        if i == 2:                  # chunk boundary: state is post-round-3
            checkpoint.save(ckpt, state, step=i + 1)
            saved["step"] = i + 1

    first = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                     rounds_per_launch=3, on_step=barrier)
    assert saved["step"] == 3
    for k in METRICS:
        np.testing.assert_allclose(first.metrics[k], full.metrics[k], **TOL)

    restored = checkpoint.restore(ckpt, tr.abstract_state(),
                                  shardings=tr.state_shardings())
    assert int(restored["step"]) == 3
    tail = run_scan(tr, plan, restored, rounds_per_launch=3, start_round=3)
    for k in ("loss", "grad_norm"):
        np.testing.assert_allclose(tail.metrics[k], full.metrics[k][3:],
                                   **TOL, err_msg=f"resumed {k}")


def test_run_grid_snapshot_resume_midgrid(tmp_path):
    """Resume edge case 1: a γ-grid run snapshotted mid-run by the async
    snapshotter restores as the already-STACKED carry and resumes ≡ the
    uninterrupted grid run (curves and final stacked states)."""
    from repro import checkpoint
    from repro.checkpoint import AsyncSnapshotter

    job = _job()
    spec = _spec(job, T=4)
    gplan = _grid_plan_for(spec, job)
    tr = _trainer(job)
    ex = PlanExecutor(tr, gplan, donate=False)
    snapdir = str(tmp_path / "grid-snaps")
    snap = AsyncSnapshotter(snapdir, 2, keep=4)
    full = ex.run_grid(tr.init_state(jax.random.PRNGKey(0)),
                       rounds_per_launch=2, snapshot=snap)
    assert full.stats.snapshots == 2              # boundaries 2 and 4

    # the stacked template gives restore the (n_grid, ...) structure
    template = ex.stack_state(tr.init_state(jax.random.PRNGKey(0)))
    restored = checkpoint.restore(str(tmp_path / "grid-snaps" /
                                      "round-00000002"), template)
    np.testing.assert_array_equal(np.asarray(restored["step"]),
                                  np.full(4, 2))
    tail = ex.run_grid(restored, rounds_per_launch=2, start_round=2)
    assert tail.metrics["loss"].shape == (4, 2)
    np.testing.assert_allclose(tail.metrics["loss"],
                               full.metrics["loss"][:, 2:], **TOL)
    for a, b in zip(jax.tree_util.tree_leaves(full.state),
                    jax.tree_util.tree_leaves(tail.state)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-7)


def test_resume_after_final_chunk_is_noop():
    """Resume edge case 2: ``start_round == rounds`` (a run restored from
    its FINAL snapshot) is an exact no-op on every lane — zero launches,
    empty curves, the carry handed back untouched."""
    job = _job()
    spec = _spec(job, T=4)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    ex = PlanExecutor(tr, plan, donate=False)
    state = tr.init_state(jax.random.PRNGKey(0))
    done = ex.run_scan(state, rounds_per_launch=2).state

    for metrics in ("chunk", "tap", "none"):
        r = ex.run_scan(done, rounds_per_launch=2, metrics=metrics,
                        start_round=4)
        assert r.launches == 0 and r.host_syncs == 0 and r.tap_events == 0
        if metrics == "none":
            assert r.metrics == {}
        else:
            assert all(len(v) == 0 for v in r.metrics.values())
        for a, b in zip(jax.tree_util.tree_leaves(done),
                        jax.tree_util.tree_leaves(r.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    r_e = ex.run_eager(done, start_round=4)
    assert r_e.launches == 0
    assert all(len(v) == 0 for v in r_e.metrics.values())

    gex = PlanExecutor(tr, _grid_plan_for(spec, job), donate=False)
    gdone = gex.run_grid(tr.init_state(jax.random.PRNGKey(0)),
                         rounds_per_launch=2)
    rg = gex.run_grid(gdone.state, rounds_per_launch=2, start_round=4)
    assert rg.launches == 0 and rg.metrics == {}
    for a, b in zip(jax.tree_util.tree_leaves(gdone.state),
                    jax.tree_util.tree_leaves(rg.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# TrainerBackend wiring
# ---------------------------------------------------------------------------
def test_backend_runtime_resolution():
    be = TrainerBackend()
    assert be.resolve_runtime(_spec(_job())) == ("scan", 8, "chunk")
    assert be.resolve_runtime(
        _spec(_job(), runtime="eager", rounds_per_launch=3,
              metrics="tap")) == ("eager", 3, "tap")
    assert TrainerBackend(runtime="eager", rounds_per_launch=2,
                          metrics="none") \
        .resolve_runtime(_spec(_job(), runtime="scan",
                               metrics="tap")) == ("eager", 2, "none")
    with pytest.raises(ValueError, match="unknown runtime"):
        _spec(_job(), runtime="vectorized")
    with pytest.raises(ValueError, match="unknown metrics"):
        _spec(_job(), metrics="streaming")
    with pytest.raises(ValueError, match="rounds_per_launch"):
        _spec(_job(), rounds_per_launch=0)


def test_backend_scan_eager_parity_and_result_roundtrip():
    """End-to-end through ``repro.api``: default scan ≡ eager oracle, the
    RunResult records the dispatch provenance, and the archived JSON
    round-trips the curves exactly."""
    job = _job()
    spec = _spec(job, T=4, rounds_per_launch=2)
    res_s = TrainerBackend().run(spec)
    res_e = TrainerBackend(runtime="eager").run(spec)
    assert res_s.extra["runtime"] == "scan"
    assert res_s.extra["rounds_per_launch"] == 2
    assert res_s.extra["metrics_mode"] == "chunk"
    # no on_step → overlapped chunks, one deferred readback
    assert res_s.extra["launches"] == 2 and res_s.extra["host_syncs"] == 1
    assert res_s.extra["tap_events"] == 0
    assert res_e.extra["runtime"] == "eager"
    assert res_e.extra["launches"] == 4 and res_e.extra["host_syncs"] == 4
    np.testing.assert_allclose(res_s.losses, res_e.losses, **TOL)
    np.testing.assert_allclose(res_s.grad_norms, res_e.grad_norms, **TOL)
    assert len(res_s.extra["metrics"]) == 4

    r2 = RunResult.from_json(res_s.to_json())
    np.testing.assert_array_equal(r2.losses, res_s.losses)
    np.testing.assert_array_equal(r2.grad_norms, res_s.grad_norms)
    assert r2.backend == "trainer"
    assert r2.extra["runtime"] == "scan"
    assert r2.schedule["tau_max"] == res_s.schedule.tau_max()


def test_backend_tap_and_none_modes():
    """Spec-level metrics selection reaches the executor: tap streams
    per-round rows to on_step (state=None), none returns no curves."""
    job = _job()
    seen = []
    res_t = TrainerBackend(
        metrics="tap",
        on_step=lambda i, st, m: seen.append((i, st))).run(
            _spec(job, T=4, rounds_per_launch=4))
    assert res_t.extra["metrics_mode"] == "tap"
    assert res_t.extra["tap_events"] == 4
    assert res_t.extra["host_syncs"] == 0
    assert [i for i, _ in seen] == list(range(4))
    assert all(st is None for _, st in seen)
    assert res_t.losses is not None and len(res_t.losses) == 4

    res_n = TrainerBackend().run(_spec(job, T=4, metrics="none"))
    assert res_n.extra["metrics_mode"] == "none"
    assert res_n.losses is None and res_n.grad_norms is None
    assert res_n.extra["metrics"] == []
    assert res_n.x is not None

    # a grid spec that misses the vmapped lane (single γ) still has to
    # SCORE runs, so the sequential fallback must override metrics="none"
    # instead of crashing on losses=None
    res_1g = TrainerBackend().run(
        _spec(job, T=4, stepsize=(3e-3,), metrics="none"))
    assert res_1g.losses is not None and len(res_1g.losses) == 4


def test_backend_grid_lane_matches_sequential_oracle():
    """End-to-end grid policy on the scan runtime: ONE vmapped program,
    same winner and same winning curves as the sequential eager-runtime
    grid loop (the oracle), per-γ curves preserved in RunResult.grid."""
    job = _job()
    spec = _spec(job, T=6, rounds_per_launch=4, stepsize=GRID_GAMMAS)
    res_g = TrainerBackend().run(spec)
    res_q = TrainerBackend(runtime="eager").run(spec)
    assert res_g.extra.get("grid_lane") and res_g.extra["n_grid"] == 4
    assert res_g.extra["launches"] == 2       # 2 chunks, ALL γ per launch
    assert set(res_g.grid) == set(GRID_GAMMAS)
    assert res_g.gamma == res_q.gamma         # same selected stepsize
    np.testing.assert_allclose(res_g.losses, res_q.losses, **TOL)
    for g in GRID_GAMMAS:
        assert res_g.grid[g]["losses"].shape == (6,)
        assert np.isfinite(res_g.grid[g]["score"])
    # an on_step consumer forces the sequential path (the lane has no
    # per-round hook)
    res_cb = TrainerBackend(on_step=lambda i, st, m: None).run(spec)
    assert not res_cb.extra.get("grid_lane")
    np.testing.assert_allclose(res_cb.losses, res_g.losses, **TOL)

    # grid-lane results archive and restore: per-γ curves exact, float
    # keys recovered, provenance fields intact
    r2 = RunResult.from_json(res_g.to_json())
    assert set(r2.grid) == set(GRID_GAMMAS)
    for g in GRID_GAMMAS:
        np.testing.assert_array_equal(r2.grid[g]["losses"],
                                      res_g.grid[g]["losses"])
        assert r2.grid[g]["score"] == res_g.grid[g]["score"]
    np.testing.assert_array_equal(r2.losses, res_g.losses)
    assert r2.extra["grid_lane"] and r2.extra["n_grid"] == 4
    assert r2.gamma == res_g.gamma


# ---------------------------------------------------------------------------
# scenario worlds on the compiled path
# ---------------------------------------------------------------------------
SCENARIO = ("straggler:k=1,factor=6,every=4,span=2;"
            "elastic:k=1,every=4,span=2;"
            "data_drift:a0=1.1,a1=2.0;"
            "sparsify:frac=0.5")


def test_scenario_channel_lowering_and_validation():
    """The extra RunPlan channels lower and validate without any executor
    work: zipf trajectories quantise into a monotone CDF bank, and
    malformed channels are rejected up front."""
    from repro.runtime import quantize_zipf_trajectory

    bank, idx = quantize_zipf_trajectory(np.linspace(1.0, 2.0, 12), 97,
                                         n_phases=4)
    assert bank.shape[1] == 97 and 2 <= bank.shape[0] <= 4
    assert idx.shape == (12,)
    assert idx.min() >= 0 and idx.max() < bank.shape[0]
    np.testing.assert_allclose(bank[:, -1], 1.0, atol=1e-5)
    assert np.all(np.diff(bank, axis=1) >= -1e-7)      # each row is a CDF
    # a constant trajectory collapses to a single phase
    b1, i1 = quantize_zipf_trajectory(np.full(5, 1.5), 97)
    assert b1.shape[0] == 1 and np.all(i1 == 0)

    job = _job()
    plan = _plan_for(_spec(job, T=4), job)
    common = dict(masks=plan.masks, delay_scales=plan.delay_scales,
                  data_keys=plan.data_keys, token_cdf=plan.token_cdf,
                  group_perms=plan.group_perms, global_batch=8, seq_len=16,
                  seed=0)
    with pytest.raises(ValueError, match="set together"):
        RunPlan(cdf_index=np.zeros(4, np.int32), **common)
    with pytest.raises(ValueError, match="out of cdf_bank range"):
        RunPlan(cdf_bank=bank, cdf_index=np.full(4, 99, np.int32), **common)
    with pytest.raises(ValueError, match=r"in \(0, 1\]"):
        RunPlan(grad_density=np.zeros(4, np.float32), **common)
    with pytest.raises(ValueError, match="grad_density"):
        RunPlan(grad_density=np.ones(3, np.float32), **common)


def test_scenario_plan_scan_matches_eager():
    """A full four-channel scenario world (straggler speeds + elastic
    membership + drifting Zipf data + top-k sparsified grads) lowers into
    ONE RunPlan, and the scan executor still matches the eager oracle —
    including rounds where elastic hard-drop zeroes a worker's mask entry
    that held a live receipt."""
    job = _job()
    spec = ExperimentSpec(scheduler="fedbuff:b=2", timing="poisson:slow=6",
                          objective=job, T=12, n_workers=4, seed=3,
                          scenario=SCENARIO)
    world = TrainerBackend.world_for(spec, 4)
    plan = compile_plan(world.schedule, job, rounds=12, n_groups=4, seed=3,
                        availability=world.availability,
                        zipf_as=world.zipf_as,
                        grad_density=world.grad_density)
    s = plan.summary()
    assert s["sparsified"] and s["n_cdf_phases"] >= 2
    # hard-drop: every (round, worker) the world marked down is zeroed...
    avail = world.availability[:12]
    assert (avail == 0).any()
    assert np.all(plan.masks[avail == 0] == 0.0)
    # ...and at least one of those entries held a receipt before the drop
    raw, _ = lower_rounds(world.schedule, 12)
    assert (raw[avail == 0] != 0).any()

    tr = _trainer(job)
    r_e = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    r_s = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                   rounds_per_launch=5)                # ragged: 5 + 5 + 2
    assert r_s.launches == 3 and r_e.launches == 12
    for k in METRICS:
        np.testing.assert_allclose(r_s.metrics[k], r_e.metrics[k], **TOL,
                                   err_msg=f"scenario metric {k}")
    pe = tr.params_of(r_e.state)
    ps = tr.params_of(r_s.state)
    for a, b in zip(jax.tree_util.tree_leaves(pe),
                    jax.tree_util.tree_leaves(ps)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# 8-virtual-device pooled scan run (ZeRO-sharded pools under shard_map)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(not MULTI, reason="needs >= 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("data,rows", [
    (4, 8),     # one group's 2 rows do not split over 4 shards: full batch
    (2, 2),     # they do over 2: gathered
])
def test_scan_pooled_multidevice_parity(data, rows):
    """Scan executor on a data × 2-model mesh with pooled ZeRO-sharded
    state ≡ the eager oracle on the same mesh, and the carried pools keep
    their sharding across chunk launches (donation must not silently
    replicate)."""
    from jax.sharding import Mesh, NamedSharding
    from repro.distributed import pooled_pspec

    mesh = Mesh(np.array(jax.devices()[:2 * data]).reshape(data, 2),
                ("data", "model"))
    job = _job(update_impl="pallas_pooled_interpret")
    spec = _spec(job, T=4)
    plan = _plan_for(spec, job)
    tr = _trainer(job, mesh=mesh)
    assert tr.pool_layout.n_shards == data
    assert PlanExecutor(tr, plan).step_rows == rows

    r_e = run_eager(tr, plan, tr.init_state(jax.random.PRNGKey(0)))
    r_s = run_scan(tr, plan, tr.init_state(jax.random.PRNGKey(0)),
                   rounds_per_launch=2)
    for k in METRICS:
        np.testing.assert_allclose(r_s.metrics[k], r_e.metrics[k], **TOL,
                                   err_msg=f"metric {k}")
    want = NamedSharding(mesh, pooled_pspec(mesh))
    for dk, grp in r_s.state["pools"].items():
        for name, buf in grp.items():
            assert buf.sharding.is_equivalent_to(want, buf.ndim), \
                f"pool {dk}/{name} lost ZeRO sharding: {buf.sharding}"
