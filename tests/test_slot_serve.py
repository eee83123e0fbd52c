"""Slot-based continuous-batching server (tentpole gates).

The two acceptance gates live here:

* **parity** — all slots filled, no arrivals, greedy: the slot lane must
  reproduce a plain loop of ``M.prefill`` and ``M.decode_step``
  token-for-token, for one arch of each family the slot server accepts.
* **no retrace on admission** — requests rotating through freed slots
  must leave the chunk/admit compile counts at one trace per program
  (the whole point of masking over control flow).

Plus the admission layer as a unit: policy parsing, arrival draws, the
scheduler-registry remap, the trace → ``Schedule`` lowering, and the
ordered-tap streaming contract.
"""
import importlib.util
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.api import ExperimentSpec, ServeJob, run
from repro.api.backends import ServeBackend
from repro.configs import get_arch
from repro.distributed import (AdmissionPolicy, AdmissionTrace, SlotConfig,
                               SlotServer, draw_arrivals, parse_admission)
from repro.models import init_params
from repro.scenarios import tau_report

TINY = dict(n_layers=1, d_model=8, n_heads=1, n_kv_heads=1, d_ff=16,
            vocab=127)
TINY_OVR = tuple(TINY.items())


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _setup(arch="qwen2-0.5b", **tiny):
    cfg = get_arch(arch).reduced().with_(remat="none", **(tiny or TINY))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(n, plen, vocab, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (n, plen)).astype(np.int32)


def _plain_loop_tokens(cfg, params, prompts, T, ctx):
    """The oracle, with no serving engine: ``chip_smoke.plain_loop_tokens``
    (each prompt prefilled alone, as the slot server admits it, so MoE
    routing sees the same rows; then T − 1 greedy ``M.decode_step`` calls
    over every row at once)."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke.plain_loop_tokens(cfg, params, prompts, T, ctx)


# ---------------------------------------------------------------------------
# acceptance gates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,tiny", [
    ("qwen2-0.5b", TINY),
    ("mamba2-370m", {}),
    ("granite-4.0-h-micro", {}),
    ("zamba2-7b", {}),
    ("deepseek-moe-16b", {}),
])
def test_slot_lane_parity_bit_for_bit(arch, tiny):
    """Full static batch, no arrivals, greedy ⇒ identical to the plain
    prefill + decode loop, for one arch of each family the slot server
    serves (dense, SSM, layer-pattern hybrid, shared-attention hybrid,
    MoE)."""
    cfg = get_arch(arch).reduced().with_(remat="none", **tiny)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, plen, T = 3, 5, 6
    ctx = plen + T
    prompts = _prompts(B, plen, cfg.vocab)
    ref = _plain_loop_tokens(cfg, params, prompts, T, ctx)
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=B, ctx_len=ctx,
                                              steps_per_launch=2))
    res = srv.serve(params, prompts, T)
    np.testing.assert_array_equal(ref, res.tokens)
    assert res.tokens.dtype == np.int32


def test_admission_does_not_retrace():
    """More requests than slots: every program stays at ONE traced
    signature while requests rotate through freed slots."""
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=16,
                                              steps_per_launch=2))
    prompts = _prompts(7, 5, cfg.vocab)
    arrivals = np.array([0, 0, 1, 3, 6, 9, 9])
    res = srv.serve(params, prompts, 6, admission="shuffled",
                    arrivals=arrivals)
    counts = srv.compile_counts()
    assert counts["chunk"] == 1, counts
    assert counts["admit"] == 1, counts
    assert counts["prefill[5]"] == 1, counts
    assert res.tokens.shape == (7, 6)
    # a second serve on the same instance reuses every compile
    srv.serve(params, prompts, 6, admission="pure")
    assert srv.compile_counts() == counts


def test_slot_serve_tap_streams_every_token():
    """The ordered io_callback tap delivers each post-admission token to
    its consumer, in per-request decode order, matching the result."""
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=16,
                                              steps_per_launch=2))
    prompts = _prompts(4, 5, cfg.vocab)
    streamed: dict = {}
    steps: dict = {}
    res = srv.serve(params, prompts, 5,
                    on_token=lambda rid, tok, step:
                    (streamed.setdefault(rid, []).append(tok),
                     steps.setdefault(rid, []).append(step)))
    for rid in range(4):
        # tokens[0] is the prefill token (emitted at admission, not
        # through the decode tap); the tap carries the remaining T-1
        np.testing.assert_array_equal(streamed[rid], res.tokens[rid, 1:])
        assert steps[rid] == sorted(steps[rid])
    assert res.tap_rows == res.decode_steps == res.chunks * 2


# ---------------------------------------------------------------------------
# serving worlds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("admission", ["pure", "random", "shuffled",
                                       "fedbuff:b=2"])
def test_every_policy_serves_every_request_once(admission):
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=16,
                                              steps_per_launch=2))
    prompts = _prompts(5, 5, cfg.vocab, seed=3)
    res = srv.serve(params, prompts, 4, admission=admission)
    assert res.tokens.shape == (5, 4)
    sched = res.schedule
    assert sorted(sched.workers.tolist()) == list(range(5))
    # realised serving concurrency can never exceed the slot count
    assert sched.tau_c() <= 2 + 1      # +1: completion-instant overlap
    rep = tau_report(sched, parse_admission(admission)[0], concurrency=2)
    assert rep["n_workers"] == 5


def test_arrivals_shift_admissions():
    """Late arrivals cannot be admitted before they arrive; with an idle
    pool the clock fast-forwards instead of launching empty chunks."""
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=24,
                                              steps_per_launch=2))
    prompts = _prompts(3, 5, cfg.vocab)
    arrivals = np.array([0, 12, 12])
    res = srv.serve(params, prompts, 4, arrivals=arrivals)
    admit = {int(w): int(a) for w, a in
             zip(res.schedule.workers,
                 np.asarray(res.ttft_steps) + arrivals)}
    assert admit[0] == 0
    assert admit[1] >= 12 and admit[2] >= 12
    assert np.all(res.ttft_steps >= 0)
    # request 0 finishes at step 3; steps 4..11 have an empty pool — the
    # loop must skip them rather than decode empty air
    assert res.decode_steps < 12 + 2 * 4


def test_single_token_budget_completes_at_admission():
    """max_new == 1: the prefill token IS the request; slots never occupy."""
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=16,
                                              steps_per_launch=2))
    prompts = _prompts(3, 5, cfg.vocab)
    res = srv.serve(params, prompts, 1)
    assert res.tokens.shape == (3, 1)
    assert res.chunks == 0 and res.tap_rows == 0
    ref = _plain_loop_tokens(cfg, params, prompts, 1, 16)
    np.testing.assert_array_equal(ref, res.tokens)


def test_slot_server_rejects_budget_overflow_and_bad_families():
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=1, ctx_len=8))
    with pytest.raises(ValueError, match="exceeds"):
        srv.serve(params, _prompts(1, 5, cfg.vocab), 4)
    vlm = get_arch("pixtral-12b").reduced()
    with pytest.raises(NotImplementedError, match="vlm"):
        SlotServer(vlm, _mesh(), SlotConfig(n_slots=1, ctx_len=8))


# ---------------------------------------------------------------------------
# backend wiring
# ---------------------------------------------------------------------------

def test_backend_slot_route_matches_lockstep_route():
    """A job without ``n_slots`` serves ``batch`` requests through
    ``batch`` slots: the same tokens as ``n_slots == batch`` set
    explicitly, and as the plain prefill + decode loop on the job's own
    prompt stream and weights."""
    job = ServeJob(batch=3, prompt_len=5, arch_overrides=TINY_OVR)
    lock = run(ExperimentSpec(objective=job, T=6))
    slot = run(ExperimentSpec(objective=ServeJob(
        batch=3, prompt_len=5, arch_overrides=TINY_OVR, n_slots=3,
        steps_per_launch=2), T=6))
    assert lock.extra["n_slots"] == 3 and lock.x.shape == (3, 6)
    np.testing.assert_array_equal(lock.x, slot.x)
    cfg = job.make_arch()
    params = init_params(cfg, jax.random.PRNGKey(0))
    ref = _plain_loop_tokens(cfg, params, lock.extra["prompts"], 6, 5 + 6)
    np.testing.assert_array_equal(ref, lock.x)
    assert slot.backend == "serve"
    assert slot.schedule is not None
    assert slot.extra["tau_report"]["global"]["tau_c"] <= 3 + 1
    assert 0 < slot.extra["occupancy"] <= 1


def test_backend_slot_route_with_arrivals_and_fedbuff():
    res = ServeBackend(mesh=_mesh()).run(ExperimentSpec(
        objective=ServeJob(batch=2, prompt_len=5, arch_overrides=TINY_OVR,
                           n_slots=2, n_requests=5,
                           admission="fedbuff:b=2",
                           arrival="poisson:gap=3", steps_per_launch=2),
        T=5, seed=2))
    assert res.x.shape == (5, 5)
    assert res.extra["n_slots"] == 2
    assert res.extra["ttft_steps"].shape == (5,)
    assert res.extra["tau_report"]["policy"] == "fedbuff"
    assert len(res.extra["arrivals"]) == 5


def test_serve_job_validates_slot_fields():
    with pytest.raises(ValueError, match="admission"):
        ServeJob(admission="nope")
    with pytest.raises(ValueError, match="arrival"):
        ServeJob(arrival="nope:gap=2")
    with pytest.raises(ValueError, match="n_slots"):
        ServeJob(n_slots=0)
    with pytest.raises(ValueError, match="steps_per_launch"):
        ServeJob(steps_per_launch=0)


# ---------------------------------------------------------------------------
# degradation: per-slot sampling, quarantine, deadlines
# ---------------------------------------------------------------------------

def test_sampled_streams_independent_of_pool_width():
    """Per-request PRNG: each request's sampled token stream is a pure
    function of (server seed, request id) — folding the slot out of the
    key — so the SAME requests decode the SAME tokens whether the pool
    has 1 slot or 2, and distinct requests get distinct streams."""
    cfg, params = _setup()
    prompts = _prompts(4, 5, cfg.vocab, seed=7)
    res = {}
    for n_slots in (1, 2):
        srv = SlotServer(cfg, _mesh(), SlotConfig(
            n_slots=n_slots, ctx_len=16, steps_per_launch=2,
            temperature=0.8, seed=11))
        res[n_slots] = srv.serve(params, prompts, 6)
        counts = srv.compile_counts()
        assert counts["chunk"] == 1 and counts["admit"] == 1, counts
    np.testing.assert_array_equal(res[1].tokens, res[2].tokens)
    # independence: no two requests share a stream (keys fold in the rid)
    toks = res[2].tokens
    for a in range(4):
        for b in range(a + 1, 4):
            assert not np.array_equal(toks[a, 1:], toks[b, 1:]), (a, b)
    # a different server seed moves the streams
    srv3 = SlotServer(cfg, _mesh(), SlotConfig(
        n_slots=2, ctx_len=16, steps_per_launch=2, temperature=0.8,
        seed=12))
    assert not np.array_equal(srv3.serve(params, prompts, 6).tokens, toks)


def test_quarantine_evicts_nonfinite_lanes():
    """Slots whose logits go non-finite are quarantined in-mask: the lane
    freezes, the request is marked evicted (its unfilled token budget is
    ``-1``), and the degradation surfaces in the trace and tau_report."""
    cfg, params = _setup()
    # poison the params: every forward produces NaN logits
    bad = jax.tree_util.tree_map(lambda x: jnp.full_like(x, np.nan), params)
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=16,
                                              steps_per_launch=2))
    prompts = _prompts(3, 5, cfg.vocab)
    res = srv.serve(bad, prompts, 5, arrivals=np.array([0, 0, 4]))
    assert sorted(res.evictions) == [0, 1, 2]     # every lane quarantined
    assert res.timeouts == {}
    # decode tokens after the eviction step are the -1 sentinel
    assert np.all(res.tokens[:, 1:] == -1)
    assert res.tokens.shape == (3, 5)
    rep = tau_report(res.schedule, "pure", evictions=res.evictions,
                     timeouts=res.timeouts)
    assert rep["degraded"]["evictions"] == {
        int(k): int(v) for k, v in res.evictions.items()}
    from repro.scenarios import render_report
    assert "evicted (quarantine)" in render_report(rep)
    # a healthy pool on the same instance: no evictions, compile reused
    ok = srv.serve(params, prompts, 5)
    assert ok.evictions == {} and np.all(ok.tokens >= 0)
    assert srv.compile_counts()["chunk"] == 1


def test_deadline_times_out_queued_requests():
    """Requests whose queue wait exceeds the deadline are cancelled at an
    admission sweep: never admitted, tokens all ``-1``, ttft ``-1``, and
    the remaining requests still serve to completion."""
    cfg, params = _setup()
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=1, ctx_len=16,
                                              steps_per_launch=2))
    prompts = _prompts(4, 5, cfg.vocab)
    res = srv.serve(params, prompts, 4, deadline=2)
    assert res.timeouts, "a 1-slot pool at deadline=2 must shed load"
    assert res.evictions == {}
    served = sorted(set(range(4)) - set(res.timeouts))
    assert served, "the head of the queue must still be served"
    for r in res.timeouts:
        assert np.all(res.tokens[r] == -1)
        assert res.ttft_steps[r] == -1
    for r in served:
        assert np.all(res.tokens[r] >= 0)
        assert res.ttft_steps[r] >= 0
    # the Schedule rows cover exactly the served requests
    assert sorted(res.schedule.workers.tolist()) == served
    rep = tau_report(res.schedule, "pure", evictions=res.evictions,
                     timeouts=res.timeouts)
    assert rep["degraded"]["timeouts"] == {
        int(k): int(v) for k, v in res.timeouts.items()}
    from repro.scenarios import render_report
    assert "timed out" in render_report(rep)
    with pytest.raises(ValueError, match="deadline"):
        srv.serve(params, prompts, 4, deadline=-1)


def test_serve_job_deadline_validation_and_backend_surface():
    with pytest.raises(ValueError, match="deadline"):
        ServeJob(deadline=-1, n_slots=2)
    # every job runs on the slot lane: no n_slots needed
    assert ServeJob(deadline=4).deadline == 4
    res = ServeBackend(mesh=_mesh()).run(ExperimentSpec(
        objective=ServeJob(batch=2, prompt_len=5, arch_overrides=TINY_OVR,
                           n_slots=1, n_requests=3, deadline=1,
                           steps_per_launch=2),
        T=4, seed=0))
    assert res.extra["timeouts"], "deadline=1 on a 1-slot pool must shed"
    assert res.extra["evictions"] == {}
    deg = res.extra["tau_report"]["degraded"]
    assert deg["timeouts"] == res.extra["timeouts"]


# ---------------------------------------------------------------------------
# admission layer units
# ---------------------------------------------------------------------------

def test_parse_admission():
    assert parse_admission("pure") == ("pure", 1)
    assert parse_admission("fedbuff:b=3") == ("fedbuff", 3)
    with pytest.raises(ValueError, match="unknown admission policy"):
        parse_admission("nope")
    with pytest.raises(ValueError, match="only b="):
        parse_admission("pure:k=2")


def test_draw_arrivals():
    assert np.array_equal(draw_arrivals(4, None), np.zeros(4))
    arr = draw_arrivals(6, "fixed:gap=3")
    assert arr[0] == 0
    assert np.array_equal(np.diff(arr), np.full(5, 3))
    pois = draw_arrivals(6, "poisson:gap=4", seed=1)
    assert pois[0] == 0 and np.all(np.diff(pois) >= 0)
    assert not np.array_equal(pois, draw_arrivals(6, "poisson:gap=4", seed=2))
    with pytest.raises(ValueError, match="unknown arrival pattern"):
        draw_arrivals(2, "zipf:gap=2")


def test_admission_policy_pure_is_fifo():
    pol = AdmissionPolicy("pure", 4)
    arrived = {0, 1, 2, 3}
    order = [pol.pick(arrived, 0) for _ in range(4)]
    assert sorted(order) == [0, 1, 2, 3]
    assert pol.pick(arrived, 0) is None     # queue drained
    assert pol.n_queued == 0


def test_admission_policy_respects_arrivals():
    pol = AdmissionPolicy("pure", 3)
    assert pol.pick(set(), 0) is None       # nothing arrived yet
    got = pol.pick({2}, 0)
    assert got == 2                          # remap lands on the arrival


def test_admission_policy_fedbuff_buffers_then_flushes():
    pol = AdmissionPolicy("fedbuff", 6, b=2, seed=0)
    arrived = set(range(6))
    # initial proposals cover every request — drain the queue through them
    first = [pol.pick(arrived, 1) for _ in range(6)]
    assert sorted(first) == list(range(6))
    assert pol.pick(arrived, 1) is None      # queue drained
    # completions buffer until b of them land, then a batch of proposals
    pol.notify_completion(first[0])
    assert not pol._proposals
    pol.notify_completion(first[1])
    assert len(pol._proposals) == 2          # fedbuff batch of b

    # the flush guard: proposals withheld + idle pool must still progress
    pol2 = AdmissionPolicy("fedbuff", 4, b=2, seed=0)
    pol2._proposals.clear()                  # simulate a withheld batch
    assert pol2.pick({0, 1}, in_flight=1) is None   # work in flight: wait
    assert pol2.pick({0, 1}, in_flight=0) == 0      # idle pool: FIFO flush


def test_admission_trace_lowers_to_schedule():
    tr = AdmissionTrace(3, wait_b=1)
    tr.admitted(0, 0)
    tr.admitted(1, 0)
    tr.completed(0, 0, 4, 2)
    tr.admitted(2, 4)
    tr.completed(1, 1, 6, 2)
    tr.completed(2, 0, 8, 1)
    s = tr.schedule()
    assert s.workers.tolist() == [0, 1, 2]
    assert s.assign_iters.tolist() == [0, 0, 1]   # rid 2 admitted after 1 done
    assert s.finish_times.tolist() == [4.0, 6.0, 8.0]
    assert s.active_jobs.tolist() == [2, 2, 1]
    assert s.n_workers == 3
    assert np.all(s.delays >= 0)
    rep = tau_report(s, "pure")
    assert rep["T"] == 3
