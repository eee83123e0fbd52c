"""Serving resilience (PR 10 tentpole gates).

Three acceptance gates live here:

* **clean-run no-op** — arming the retry machinery on a clean world is
  token-identical to the plain serve (and the compile counts stay at one
  trace per program).
* **SIGKILL crash-resume** — a subprocess serving with async snapshots
  (decode state + host ledger) is SIGKILLed mid-run; this process resumes
  from the newest restorable snapshot and the completed serve's token
  matrix is bitwise identical to an uninterrupted run.
* **chaos soak** — poison + driver preemption + bursty overload composed
  through the fault grammar: every request ends completed or accounted in
  exactly one degraded bucket (evictions / timeouts / shed / drained) —
  no silent loss.

Plus the mechanism units: deterministic backoff, prefix replay through
prefill, retry exhaustion, deadline=0, shed policies, graceful drain,
ledger/policy snapshot round-trips, and the ``ServeJob`` surface.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import jax
import pytest
from jax.sharding import Mesh

from repro.api import ExperimentSpec, ServeJob, run
from repro.api.backends import ServeBackend
from repro.checkpoint import AsyncSnapshotter
from repro.configs import get_arch
from repro.core.delays import TimingModel
from repro.distributed import (OverloadPolicy, RetryPolicy, ServePreempted,
                               SlotConfig, SlotServer)
from repro.distributed.slot_serve import _Ledger
from repro.faults import ServeFaults, realise_serve_faults
from repro.models import init_params
from repro.obs import Recorder
from repro.scenarios import tau_report, render_report

TINY = dict(n_layers=1, d_model=8, n_heads=1, n_kv_heads=1, d_ff=16,
            vocab=127)
TINY_OVR = tuple(TINY.items())


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _setup():
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none", **TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(n, plen, vocab, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (n, plen)).astype(np.int32)


def _server(cfg, n_slots, ctx, K=2, recorder=None, temperature=0.0):
    return SlotServer(cfg, _mesh(),
                      SlotConfig(n_slots=n_slots, ctx_len=ctx,
                                 temperature=temperature,
                                 steps_per_launch=K), recorder=recorder)


def _accounted(res, n_req):
    """Every rid lands in exactly ONE terminal bucket (full row counts as
    'completed'); returns the per-rid bucket map."""
    buckets = {}
    for rid in range(n_req):
        hits = [name for name, m in (("evicted", res.evictions),
                                     ("timed_out", res.timeouts),
                                     ("shed", res.shed),
                                     ("drained", res.drained)) if rid in m]
        if not hits:
            assert (res.tokens[rid] >= 0).all(), (
                f"rid {rid} is in no degraded bucket but its row is not a "
                f"full token row: {res.tokens[rid]}")
            buckets[rid] = "completed"
        else:
            assert len(hits) == 1, f"rid {rid} in several buckets: {hits}"
            buckets[rid] = hits[0]
    return buckets


# ---------------------------------------------------------------------------
# policies + timing registry units
# ---------------------------------------------------------------------------

def test_retry_policy_backoff_and_validation():
    rp = RetryPolicy(max_attempts=3, backoff_base=4, backoff_factor=2.0)
    assert [rp.backoff_steps(f) for f in (1, 2, 3)] == [4, 8, 16]
    assert RetryPolicy(backoff_base=0).backoff_steps(5) == 0
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="backoff_factor"):
        RetryPolicy(backoff_factor=0.5)
    with pytest.raises(ValueError, match="queue_cap"):
        OverloadPolicy(0)
    with pytest.raises(ValueError, match="shed policy"):
        OverloadPolicy(2, shed="nope")


def test_bursty_timing_pattern():
    """The bursty arrival model: near-zero gaps inside a burst, 4·s
    between bursts — same mean as the base gap; batch draws must equal
    the scalar oracle draw-for-draw."""
    s = 3.0
    tm = TimingModel(np.full(64, s), "bursty", seed=7)
    batch = tm.sample_round(np.arange(64))
    oracle = TimingModel(np.full(64, s), "bursty", seed=7)
    scalar = np.array([oracle.sample(i) for i in range(64)])
    np.testing.assert_allclose(batch, scalar)
    assert set(np.round(batch, 8)) <= {4.0 * s, 1e-6}
    assert (batch < 1e-3).any(), "no burst (near-zero gap) realised"
    assert (batch > s).any(), "no inter-burst gap realised"
    # replays bit-identically
    np.testing.assert_array_equal(
        batch, TimingModel(np.full(64, s), "bursty", seed=7)
        .sample_round(np.arange(64)))


def test_serve_fault_grammar():
    f = realise_serve_faults(
        "slot_poison:rid=1,step=4,every=0;serve_preempt:at=6,every=0",
        n_requests=4, horizon=16)
    assert f.poisons == ((1, 4),)
    assert f.preempt_steps == (6,)
    assert not f.empty
    # every>0 expands on the decode-step clock up to the horizon
    f2 = realise_serve_faults("slot_poison:rid=0,step=2,every=4",
                              n_requests=2, horizon=12)
    assert f2.poisons == ((0, 2), (0, 6), (0, 10))
    # training-lane transforms contribute no serve channels
    f3 = realise_serve_faults("nan_grad:k=1,every=4", n_requests=2,
                              horizon=8)
    assert f3.empty
    with pytest.raises(ValueError, match="rid"):
        realise_serve_faults("slot_poison:rid=-1", 2, 8)
    with pytest.raises(ValueError, match="at"):
        realise_serve_faults("serve_preempt:at=0", 2, 8)


def test_ledger_json_roundtrip():
    L = _Ledger(3, 2, [0, 1, 5])
    L.t, L.chunks, L.busy_steps = 4, 2, 7
    L.slot_rid = [1, -1]
    L.state_of = {0: "done", 1: "inflight", 2: "queued"}
    L.fin = {0: 3, 1: 6}
    L.admit_t = {0: 0, 1: 2}
    L.tries = {2: 1}
    L.emitted = {2: [5, 9]}
    L.outputs = {1: [7, 8, 9]}
    L.cur_evict = {2: 3}
    L.evict_events = [[2, 3]]
    L.evt_cursor = 1
    L.evictions, L.drain_t = {}, None
    d = L.to_json()
    L2 = _Ledger.from_json(d)
    assert L2.to_json() == d
    assert L2.in_flight == 1 and L2.done == 1
    assert L2.state_of == L.state_of and L2.emitted == L.emitted


def test_admission_policy_state_roundtrip():
    from repro.distributed import AdmissionPolicy

    a = AdmissionPolicy("shuffled", 6, seed=3)
    b = AdmissionPolicy("shuffled", 6, seed=99)     # scrambled on purpose
    arrived = set(range(6))
    first = a.pick(arrived, 0)
    a.notify_completion(first)
    b.load_state(a.state_dict())
    for _ in range(3):                              # identical continuations
        pa = a.pick(arrived, 1)
        pb = b.pick(arrived, 1)
        assert pa == pb
        if pa is not None:
            a.notify_completion(pa)
            b.notify_completion(pb)


# ---------------------------------------------------------------------------
# acceptance gate: clean-world retry no-op
# ---------------------------------------------------------------------------

def test_clean_world_retry_is_token_identical():
    cfg, params = _setup()
    n, plen, T = 3, 4, 6
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    arr = np.array([0, 1, 3])
    plain = _server(cfg, 2, ctx).serve(params, prompts, T, arrivals=arr)
    srv = _server(cfg, 2, ctx)
    armed = srv.serve(params, prompts, T, arrivals=arr,
                      retry=RetryPolicy(max_attempts=3),
                      overload=OverloadPolicy(queue_cap=8))
    np.testing.assert_array_equal(plain.tokens, armed.tokens)
    assert armed.evictions == {} and armed.attempts == {}
    assert armed.shed == {} and armed.drained == {}
    assert armed.resumed_from is None
    assert all(v == 1 for v in srv.compile_counts().values()), (
        srv.compile_counts())


# ---------------------------------------------------------------------------
# retry mechanism
# ---------------------------------------------------------------------------

def test_poison_retry_recovers_full_row():
    """A poisoned lane retries with its emitted prefix replayed through
    prefill; under greedy decoding the recovered row equals the clean
    row exactly."""
    cfg, params = _setup()
    n, plen, T = 2, 4, 6
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    clean = _server(cfg, 2, ctx).serve(params, prompts, T)
    res = _server(cfg, 2, ctx).serve(
        params, prompts, T,
        faults=ServeFaults(poisons=((1, 2),)),
        retry=RetryPolicy(max_attempts=2, backoff_base=2))
    np.testing.assert_array_equal(clean.tokens, res.tokens)
    assert res.attempts == {1: 1}
    assert res.evictions == {}          # recovered — not terminal
    assert _accounted(res, n) == {0: "completed", 1: "completed"}


def test_without_retry_poison_is_terminal():
    cfg, params = _setup()
    n, plen, T = 2, 4, 6
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    res = _server(cfg, 2, ctx).serve(params, prompts, T,
                                     faults=ServeFaults(poisons=((1, 2),)))
    assert res.evictions == {1: 2}
    assert (res.tokens[1, :3] >= 0).all() and (res.tokens[1, 3:] == -1).all()
    assert (res.tokens[0] >= 0).all()


def test_retry_exhaustion_lands_in_evictions_with_attempts():
    """slot_poison every=1 fails every attempt: the request exhausts its
    budget and is accounted terminally with the attempt count."""
    cfg, params = _setup()
    n, plen, T = 1, 4, 4
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    cells = tuple((0, s) for s in range(1, 32))          # poison steps >= 1
    res = _server(cfg, 1, ctx).serve(
        params, prompts, T, faults=ServeFaults(poisons=cells),
        retry=RetryPolicy(max_attempts=2, backoff_base=1))
    assert 0 in res.evictions
    assert res.attempts == {0: 2}
    row = res.tokens[0]
    k = int((row >= 0).sum())
    assert 0 < k < T and (row[:k] >= 0).all() and (row[k:] == -1).all(), row
    assert _accounted(res, n) == {0: "evicted"}


def test_retried_stream_reseeds_per_attempt():
    """Attempt a re-seeds the slot key with fold_in(key, a): under
    temperature sampling the retried tail is reproducible run-to-run."""
    cfg, params = _setup()
    n, plen, T = 1, 4, 6
    ctx = plen + T

    def go():
        return _server(cfg, 1, ctx, temperature=0.8).serve(
            params, _prompts(n, plen, cfg.vocab), T,
            faults=ServeFaults(poisons=((0, 2),)),
            retry=RetryPolicy(max_attempts=2, backoff_base=2))

    a, b = go(), go()
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert a.attempts == {0: 1} and (a.tokens[0] >= 0).all()


# ---------------------------------------------------------------------------
# deadlines + overload + drain
# ---------------------------------------------------------------------------

def test_deadline_zero_times_out_at_first_sweep():
    cfg, params = _setup()
    n, plen, T = 3, 4, 4
    ctx = plen + T
    res = _server(cfg, 1, ctx).serve(params, _prompts(n, plen, cfg.vocab),
                                     T, deadline=0)
    # one admitted at t=0; the two still queued at the next sweep (wait
    # K > 0) are immediately timed out
    assert len(res.timeouts) == 2
    assert set(res.timeouts.values()) == {2}
    assert sorted(v for r, v in enumerate(res.ttft_steps) if v < 0) == [-1, -1]
    assert _accounted(res, n)[0] == "completed"


def test_deadline_timeout_retries_with_backoff_then_completes():
    cfg, params = _setup()
    n, plen, T = 2, 4, 4
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    clean = _server(cfg, 2, ctx).serve(params, prompts, T)
    res = _server(cfg, 1, ctx).serve(
        params, prompts, T, deadline=0,
        retry=RetryPolicy(max_attempts=3, backoff_base=2))
    assert res.timeouts == {} and res.attempts.get(1, 0) >= 1
    # greedy: the eventually-admitted stream matches the clean one
    np.testing.assert_array_equal(clean.tokens, res.tokens)
    assert _accounted(res, n) == {0: "completed", 1: "completed"}


def test_shed_policies_are_distinguishable():
    cfg, params = _setup()
    n, plen, T = 6, 4, 4
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)

    def go(shed):
        return _server(cfg, 1, ctx).serve(
            params, prompts, T,
            overload=OverloadPolicy(queue_cap=2, shed=shed))

    new = go("reject-new")
    old = go("drop-oldest")
    assert len(new.shed) == 3 and len(old.shed) == 3
    assert set(new.shed) != set(old.shed)
    # reject-new drops the NEWEST eligible waiters, drop-oldest the head
    assert set(new.shed) == {3, 4, 5}
    assert set(old.shed) == {1, 2, 3}
    for res in (new, old):
        b = _accounted(res, n)
        assert sum(1 for v in b.values() if v == "completed") == 3


def test_readmission_respects_drop_oldest_shedding():
    """A retried request re-enters a bounded queue: under drop-oldest its
    later eligibility makes it the freshest waiter, so the head sheds —
    and every request is still accounted."""
    cfg, params = _setup()
    n, plen, T = 4, 4, 4
    ctx = plen + T
    res = _server(cfg, 1, ctx).serve(
        params, _prompts(n, plen, cfg.vocab), T,
        faults=ServeFaults(poisons=((0, 1), (0, 2), (0, 3), (0, 4),
                                    (0, 5), (0, 6), (0, 7))),
        retry=RetryPolicy(max_attempts=2, backoff_base=2),
        overload=OverloadPolicy(queue_cap=1, shed="drop-oldest"))
    buckets = _accounted(res, n)
    assert buckets[0] in ("evicted", "shed")     # rid 0 fails every attempt
    assert res.shed, "cap=1 on a 1-slot pool must shed someone"
    assert res.attempts.get(0, 0) >= 1


def test_graceful_drain():
    cfg, params = _setup()
    n, plen, T = 4, 4, 6
    ctx = plen + T
    rec = Recorder()
    arr = np.array([0, 0, 8, 12])
    res = _server(cfg, 1, ctx, recorder=rec).serve(
        params, _prompts(n, plen, cfg.vocab), T, arrivals=arr,
        drain_after=2)
    # rid 0 is in flight at the drain point and finishes; everyone still
    # queued (arrived or not) is cancelled and accounted
    assert (res.tokens[0] >= 0).all()
    assert set(res.drained) == {1, 2, 3}
    assert all(v == 2 for v in res.drained.values())
    names = {e["name"] for e in rec.tracer.chrome_trace()["traceEvents"]}
    assert "drain" in names and "drain_start" in names
    b = _accounted(res, n)
    assert b == {0: "completed", 1: "drained", 2: "drained", 3: "drained"}


def test_serve_job_resilience_fields_and_backend_surface():
    with pytest.raises(ValueError, match="max_retries"):
        ServeJob(max_retries=0)
    # every job runs on the slot lane: no n_slots needed
    assert ServeJob(max_retries=2).max_retries == 2
    assert ServeJob(queue_cap=4).queue_cap == 4
    with pytest.raises(ValueError, match="queue_cap"):
        ServeJob(queue_cap=0, n_slots=2)
    with pytest.raises(ValueError, match="shed policy"):
        ServeJob(queue_cap=2, n_slots=2, shed_policy="nope")
    with pytest.raises(ValueError, match="drain_after"):
        ServeJob(drain_after=-1, n_slots=2)
    res = ServeBackend(mesh=_mesh()).run(ExperimentSpec(
        objective=ServeJob(batch=2, prompt_len=4, arch_overrides=TINY_OVR,
                           n_slots=2, n_requests=3, max_retries=2,
                           retry_backoff=2, queue_cap=4,
                           steps_per_launch=2),
        T=5, seed=0, scenario="slot_poison:rid=1,step=2,every=0"))
    assert res.extra["attempts"] == {1: 1}
    assert res.extra["evictions"] == {}            # recovered via retry
    assert (res.x >= 0).all()
    deg = res.extra["tau_report"]["degraded"]
    assert deg["attempts"] == {1: 1}
    assert "shed" in deg and "drained" in deg


def test_tau_report_degraded_render():
    lock = run(ExperimentSpec(objective=ServeJob(
        batch=2, prompt_len=4, arch_overrides=TINY_OVR, n_slots=2,
        steps_per_launch=2), T=4))
    rep = tau_report(lock.schedule, "pure", concurrency=2,
                     evictions={0: 3}, timeouts={1: 2}, shed={2: 1},
                     drained={3: 4}, attempts={0: 2})
    assert rep["degraded"]["shed"] == {2: 1}
    assert rep["degraded"]["attempts"] == {0: 2}
    txt = render_report(rep)
    assert "1 shed" in txt and "1 drained" in txt
    assert "1 retried" in txt and "2 failed attempts" in txt


# ---------------------------------------------------------------------------
# durability: snapshot / preempt / resume
# ---------------------------------------------------------------------------

def test_preempt_snapshot_resume_bitwise(tmp_path):
    """serve_preempt raises at the scheduled boundary after a forced
    snapshot offer; a resumed serve completes with a token matrix bitwise
    identical to the uninterrupted run."""
    cfg, params = _setup()
    n, plen, T = 3, 4, 6
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    arr = np.array([0, 0, 4])
    clean = _server(cfg, 2, ctx).serve(params, prompts, T, arrivals=arr)

    srv = _server(cfg, 2, ctx)
    snapdir = str(tmp_path / "serve-snaps")
    faults = ServeFaults(preempt_steps=(4,))
    with pytest.raises(ServePreempted) as ei:
        srv.serve(params, prompts, T, arrivals=arr, faults=faults,
                  snapshot=AsyncSnapshotter(snapdir, 2, keep=3))
    assert ei.value.at == 4 and ei.value.step >= 4
    r, latest = AsyncSnapshotter.latest(snapdir)
    assert r == ei.value.step

    res = srv.serve(params, prompts, T, arrivals=arr, faults=faults,
                    resume_from=latest)
    assert res.resumed_from == r
    np.testing.assert_array_equal(clean.tokens, res.tokens)
    np.testing.assert_array_equal(clean.ttft_steps, res.ttft_steps)
    assert res.chunks == clean.chunks              # lifetime accounting


def _sigkill_child_main(snapdir):                  # pragma: no cover
    cfg, params = _setup()
    n, plen, T = 4, 4, 12
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    srv = _server(cfg, 2, ctx, K=2)

    def throttle(rid, tok, step):                  # ~0.2 s per token: the
        time.sleep(0.2)                            # parent kills mid-serve

    srv.serve(params, prompts, T, arrivals=np.array([0, 0, 4, 8]),
              on_token=throttle,
              snapshot=AsyncSnapshotter(snapdir, 2, keep=3))
    print("FINISHED", flush=True)


def test_sigkill_serve_crash_resume_gate(tmp_path):
    """The serving durability acceptance gate: SIGKILL a subprocess
    mid-serve, resume from its newest restorable snapshot, and the
    completed token matrix is bitwise identical to an uninterrupted
    run — pre-crash tokens ride the snapshot's host ledger."""
    snapdir = str(tmp_path / "serve-crash")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""),
                    os.path.dirname(os.path.abspath(__file__))) if p)
    child = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; from test_resilience import _sigkill_child_main; "
         "_sigkill_child_main(sys.argv[1])", snapdir],
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 300
        found = None
        while time.time() < deadline:
            if child.poll() is not None:
                break
            found = AsyncSnapshotter.latest(snapdir)
            if found is not None:
                break
            time.sleep(0.05)
        assert found is not None, (
            "child produced no snapshot before finishing/deadline:\n"
            + child.communicate()[1])
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=60)
    out = (child.stdout.read() or "") if child.stdout else ""
    assert "FINISHED" not in out, "child finished before the kill landed"

    r, latest = AsyncSnapshotter.latest(snapdir)
    assert r > 0 and r % 2 == 0                    # chunk boundary

    cfg, params = _setup()
    n, plen, T = 4, 4, 12
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    arr = np.array([0, 0, 4, 8])
    clean = _server(cfg, 2, ctx, K=2).serve(params, prompts, T,
                                            arrivals=arr)
    res = _server(cfg, 2, ctx, K=2).serve(params, prompts, T, arrivals=arr,
                                          resume_from=latest)
    assert res.resumed_from == r
    np.testing.assert_array_equal(clean.tokens, res.tokens)
    np.testing.assert_array_equal(clean.ttft_steps, res.ttft_steps)


# ---------------------------------------------------------------------------
# acceptance gate: chaos soak
# ---------------------------------------------------------------------------

def test_chaos_soak_no_silent_loss(tmp_path):
    """Poison + driver preemption + bursty arrivals + bounded queue +
    retries, composed through the fault grammar and resumed across the
    preemption: every request is completed or accounted in exactly one
    degraded bucket, and the τ-report's degraded section agrees."""
    cfg, params = _setup()
    n, plen, T = 6, 4, 5
    ctx = plen + T
    prompts = _prompts(n, plen, cfg.vocab)
    from repro.distributed import draw_arrivals

    arr = draw_arrivals(n, "bursty:gap=2", seed=3)
    faults = realise_serve_faults(
        "slot_poison:rid=1,step=3,every=1;serve_preempt:at=8,every=0",
        n_requests=n, horizon=256, seed=3)
    assert faults.poisons and faults.preempt_steps == (8,)

    srv = _server(cfg, 2, ctx)
    snapdir = str(tmp_path / "chaos-snaps")
    resume, res, hops = None, None, 0
    while True:
        try:
            res = srv.serve(params, prompts, T, arrivals=arr,
                            faults=faults,
                            retry=RetryPolicy(max_attempts=2,
                                              backoff_base=2),
                            overload=OverloadPolicy(queue_cap=3,
                                                    shed="drop-oldest"),
                            snapshot=AsyncSnapshotter(snapdir, 2, keep=3),
                            resume_from=resume)
            break
        except ServePreempted:
            hops += 1
            assert hops <= 2, "preemption loop did not converge"
            resume = AsyncSnapshotter.latest(snapdir)[1]
    assert hops == 1 and res.resumed_from is not None

    buckets = _accounted(res, n)                   # the no-silent-loss gate
    assert buckets[1] != "completed"               # poisoned every step
    assert res.attempts.get(1, 0) >= 1
    rep = tau_report(res.schedule, "pure", concurrency=2,
                     scenario_spec="chaos", evictions=res.evictions,
                     timeouts=res.timeouts, shed=res.shed,
                     drained=res.drained, attempts=res.attempts)
    deg = rep["degraded"]
    n_degraded = sum(1 for v in buckets.values() if v != "completed")
    assert (len(deg["evictions"]) + len(deg["timeouts"])
            + len(deg["shed"]) + len(deg["drained"])) == n_degraded
    assert render_report(rep)                      # renders without error
