"""Suite for ``repro.obs`` — the tracing + metrics layer.

The two load-bearing contracts:

* **observability never changes computed bits** — a run with a Recorder
  attached produces bit-identical state/curves to the same run without
  one (the tracer reads host boundaries that already exist; it never
  adds a device sync), and
* **the exports are real formats** — ``trace.json`` is structurally
  valid Chrome trace-event JSON (what Perfetto loads) and the JSONL
  metrics log round-trips through its own versioned schema validator.

Plus unit coverage for the Tracer primitives, the CompileWatch retrace
sentinel, and the end-to-end wiring (executor counters match ExecStats,
SlotServer trace carries the admission story, snapshot spans show the
async overlap, ``extra["obs"]`` survives RunResult JSON round-trips).
"""
import json
import pathlib
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.api import ExperimentSpec, RunResult, TrainJob, TrainerBackend
from repro.obs import (CompileWatch, METRICS_SCHEMA_VERSION, Recorder,
                       RetraceError, SchemaError, Tracer, compile_log,
                       render_summary, span, validate_line, validate_lines,
                       validate_metrics_log)
from repro.obs import schema as obs_schema
from repro.runtime import PlanExecutor, compile_plan

MICRO = (("n_layers", 1), ("d_model", 64), ("n_heads", 2), ("n_kv_heads", 1),
         ("d_ff", 64), ("vocab", 97))
TOL = dict(rtol=1e-5, atol=1e-7)


def _job(**kw):
    kw.setdefault("arch", "qwen2-0.5b")
    kw.setdefault("global_batch", 8)
    kw.setdefault("seq_len", 16)
    kw.setdefault("arch_overrides", MICRO)
    return TrainJob(**kw)


def _spec(job, T=6, **kw):
    return ExperimentSpec(scheduler="shuffled", timing="poisson:slow=6",
                          objective=job, T=T, n_workers=4, seed=0,
                          stepsize=3e-3, **kw)


def _trainer(job):
    from jax.sharding import Mesh
    from repro.distributed import AsyncTrainer, AsyncConfig
    from repro.optim import OptConfig

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    tr = AsyncTrainer(job.make_arch(), mesh,
                      opt=OptConfig(lr=3e-3, clip_norm=job.clip_norm),
                      async_cfg=AsyncConfig(delay_rounds=job.delay_rounds))
    tr.n_groups = 4
    return tr


def _plan_for(spec, job):
    _, schedule = TrainerBackend.masks_for(spec, 4)
    return compile_plan(schedule, job, rounds=spec.T, n_groups=4,
                        seed=spec.seed)


def _assert_states_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Tracer primitives
# ---------------------------------------------------------------------------

def test_tracer_primitives_and_phase_table():
    tr = Tracer()
    with tr.span("launch", "executor", chunk=0):
        pass
    with tr.span("launch", "executor", chunk=1):
        pass
    t0 = tr.now_ns()
    tr.span_at("request", "slot0", t0, t0 + 3_000_000, rid=7)
    tr.instant("tap_round", lane="tap", round=0)
    tr.count("rounds", 5)
    tr.count("rounds", 3)
    tr.gauge("occupancy", 0.5, lane="server")
    tr.hist("ttft_steps", 1.0)
    tr.hist("ttft_steps", 3.0)

    phases = tr.phase_table()
    assert phases["launch"]["count"] == 2
    assert phases["request"]["count"] == 1
    assert phases["request"]["total_s"] == pytest.approx(0.003)
    assert tr.counters() == {"rounds": 8}
    h = tr.hist_summaries()["ttft_steps"]
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 3.0
    assert h["mean"] == pytest.approx(2.0)
    assert tr.wall_s > 0


def test_chrome_trace_structure():
    """The envelope Perfetto's loader accepts: M thread-name metadata per
    lane, X spans with µs ts/dur, thread-scoped instants, C counters."""
    tr = Tracer()
    with tr.span("launch", "executor", lo=0, hi=4):
        pass
    tr.instant("compile", lane="compile", fn="chunk[tap]",
               signatures=np.int64(2))       # numpy arg must degrade
    tr.gauge("gscale", 0.5, lane="faults")
    doc = tr.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    ev = doc["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    assert {"repro"} == {e["args"]["name"] for e in meta
                         if e["name"] == "process_name"}
    lanes = {e["args"]["name"]: e["tid"] for e in meta
             if e["name"] == "thread_name"}
    assert set(lanes) == {"executor", "compile", "faults"}
    (x,) = [e for e in ev if e["ph"] == "X"]
    assert x["name"] == "launch" and x["tid"] == lanes["executor"]
    assert x["dur"] >= 0 and x["args"] == {"lo": 0, "hi": 4}
    (i,) = [e for e in ev if e["ph"] == "i"]
    assert i["s"] == "t" and i["args"]["signatures"] == 2.0
    (c,) = [e for e in ev if e["ph"] == "C"]
    assert c["args"] == {"gscale": 0.5}
    json.dumps(doc)                          # numpy degraded, serialisable


def test_span_survives_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("launch", "executor"):
            raise ValueError("boom")
    assert tr.phase_table()["launch"]["count"] == 1


# ---------------------------------------------------------------------------
# the metrics log schema
# ---------------------------------------------------------------------------

def test_metrics_log_round_trip(tmp_path):
    tr = Tracer()
    tr.count("rounds", 6)
    tr.count("launches", 2)
    tr.gauge("occupancy", 0.75, lane="server")
    tr.hist("ttft_steps", 2.0)
    path = tr.export_metrics(str(tmp_path / "m.jsonl"))
    counts = validate_metrics_log(path)
    assert counts == {"header": 1, "gauge": 1, "counter": 2, "hist": 1}
    first = json.loads(open(path).readline())
    assert first["kind"] == "header" and first["v"] == METRICS_SCHEMA_VERSION


def test_schema_rejects_bad_lines():
    ok = {"v": 1, "kind": "counter", "name": "rounds", "value": 6}
    assert validate_line(ok) == "counter"
    with pytest.raises(SchemaError, match="schema version"):
        validate_line({**ok, "v": 2})
    with pytest.raises(SchemaError, match="unknown kind"):
        validate_line({**ok, "kind": "summary"})
    with pytest.raises(SchemaError, match="missing"):
        validate_line({"v": 1, "kind": "counter", "name": "rounds"})
    # bool is an int subclass — numeric fields must still reject it
    with pytest.raises(SchemaError, match="bool"):
        validate_line({**ok, "value": True})


def test_schema_structural_rules():
    head = {"v": 1, "kind": "header", "source": "t", "wall_s": 0.1,
            "created_unix": 1.0}
    cnt = {"v": 1, "kind": "counter", "name": "r", "value": 1}
    assert validate_lines([head, cnt]) == {"header": 1, "counter": 1}
    with pytest.raises(SchemaError, match="header"):
        validate_lines([cnt])                        # no header at all
    with pytest.raises(SchemaError, match="line 1"):
        validate_lines([cnt, head])                  # header not first
    with pytest.raises(SchemaError, match="unique"):
        validate_lines([head, head])


def test_schema_cli_gate(tmp_path):
    """``python -m repro.obs.schema`` is the CI gate: exit 0 + a count
    line on a valid log, non-zero on a corrupt one."""
    tr = Tracer()
    tr.count("rounds", 1)
    good = tr.export_metrics(str(tmp_path / "good.jsonl"))
    obs_schema.main([good])                          # must not raise
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 99, "kind": "counter"}\n')
    with pytest.raises(SchemaError):
        obs_schema.main([str(bad)])
    root = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, "-m", "repro.obs.schema", str(bad)],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": str(root / "src"),
                            "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0


# ---------------------------------------------------------------------------
# CompileWatch: the generalised retrace sentinel
# ---------------------------------------------------------------------------

class _FakeJit:
    """Stands in for a jax.jit callable: grows a traced-signature set."""

    def __init__(self):
        self._sigs = set()

    def __call__(self, x):
        self._sigs.add(np.asarray(x).shape)
        return x

    def _cache_size(self):
        return len(self._sigs)


def test_compile_watch_records_growth():
    rec = Recorder()
    watch = CompileWatch(rec)
    fn = watch.wrap("chunk", _FakeJit())
    assert fn.__wrapped_jit__ is not None
    assert fn(np.zeros(3)) is not None               # first trace
    fn(np.zeros(3))                                  # cache hit: no event
    fn(np.zeros((2, 2)))                             # retrace
    assert watch.counts() == {"chunk": 2}
    assert rec.tracer.counters()["compiles"] == 2
    compiles = [e for e in rec.tracer.chrome_trace()["traceEvents"]
                if e.get("name") == "compile"]
    assert len(compiles) == 2
    assert compiles[-1]["args"] == {"fn": "chunk", "signatures": 2}


def test_compile_watch_steady_contract():
    watch = CompileWatch()
    fn = watch.wrap("chunk", _FakeJit())
    with pytest.raises(RetraceError, match="before mark_steady"):
        watch.check_steady()
    fn(np.zeros(3))
    assert watch.mark_steady() == {"chunk": 1}
    fn(np.zeros(3))
    watch.check_steady()                             # warm reuse: fine
    fn(np.zeros(5))                                  # steady-state retrace
    with pytest.raises(RetraceError, match=r"chunk: 1 -> 2"):
        watch.check_steady()


def test_compile_watch_unsizeable_fn_degrades():
    watch = CompileWatch()
    watch.register("plain", lambda x: x)             # no _cache_size
    assert watch.counts() == {"plain": -1}
    watch.observe()                                  # must not raise
    # wrapped, its compile is still credited, once: the later calls only
    # look their jaxpr up again (an ordered io_callback keeps the jit off
    # JAX's C++ dispatch path), and a lookup alone is no compile
    tapped = _tapped_jit(2.0)
    fn = watch.wrap("unsized", lambda x: tapped(x))
    x = jax.numpy.ones(5)
    n0 = len(compile_log())
    fn(x)
    mine = compile_log()[n0:]
    assert {e.program for e in mine} == {"unsized"}
    assert "backend" in {e.phase for e in mine}
    n1 = len(compile_log())
    for _ in range(3):
        fn(x)
    jax.effects_barrier()
    assert len(compile_log()) == n1


def _tapped_jit(c):
    from jax.experimental import io_callback
    return jax.jit(lambda x: io_callback(lambda v: None, None, x,
                                         ordered=True) or x + c)


def test_compile_log_credits_the_watched_program():
    rec = Recorder()
    watch = CompileWatch(rec)
    fn = watch.wrap("probe", jax.jit(lambda x: x * 3.0 + 1.0))
    x = jax.numpy.ones(7)
    n0 = len(compile_log())
    fn(x)
    mine = compile_log()[n0:]
    assert mine and {e.program for e in mine} == {"probe"}
    assert {"trace", "lower", "backend"} <= {e.phase for e in mine}
    assert sum(e.end_s - e.start_s for e in mine
               if e.phase == "backend") > 0
    assert all(e.end_s >= e.start_s for e in mine)
    n1 = len(compile_log())
    fn(x)                                          # warm: nothing compiles
    assert len(compile_log()) == n1
    # with a recorder: one compile span per entry, and the seconds
    assert rec.tracer.phase_table()["compile"]["count"] == len(mine)
    assert 0 < rec.tracer.counters()["compile_s"] \
        <= max(e.end_s for e in mine) - min(e.start_s for e in mine)

    # an ordered io_callback keeps a jit off JAX's C++ dispatch path, so
    # every call looks its jaxpr up again; a call that compiles nothing
    # credits nothing
    tapped = watch.wrap("tapped", _tapped_jit(1.0))
    tapped(x)
    n2 = len(compile_log())
    tapped(x)
    jax.effects_barrier()
    assert len(compile_log()) == n2
    n1 = n2

    jax.jit(lambda x: x - 7.0)(x)                  # no watch sees this one
    unwatched = compile_log()[n1:]
    assert unwatched and {e.program for e in unwatched} == {None}
    assert "backend" in {e.phase for e in unwatched}


def test_compile_log_keeps_unwatched_compiles_not_lookups():
    """An unwatched jit off the C++ dispatch path logs its one compile
    (trace with it) under ``None``, and nothing for later calls."""
    tapped = _tapped_jit(3.0)
    x = jax.numpy.ones(6)
    n0 = len(compile_log())
    tapped(x)
    mine = compile_log()[n0:]
    assert {e.program for e in mine} == {None}
    assert {"trace", "lower", "backend"} <= {e.phase for e in mine}
    n1 = len(compile_log())
    for _ in range(3):
        tapped(x)
    jax.effects_barrier()
    assert len(compile_log()) == n1


def test_span_is_a_profiler_annotation(tmp_path):
    """Without a recorder the span still lands in the profiler's host
    plane as ``lane.name``; with one, the Tracer records it unchanged."""
    from jax.profiler import ProfileData

    rec = Recorder()
    with jax.profiler.trace(str(tmp_path)):
        with span(None, "probe", "lane"):
            jax.numpy.ones(3).block_until_ready()
        with span(rec, "probe_rec", "lane", rid=3):
            pass
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"lane.probe", "lane.probe_rec"} <= names
    assert rec.tracer.phase_table()["probe_rec"]["count"] == 1
    (x,) = [e for e in rec.tracer.chrome_trace()["traceEvents"]
            if e["ph"] == "X"]
    assert (x["name"], x["cat"], x["args"]) == ("probe_rec", "lane",
                                                {"rid": 3})


# ---------------------------------------------------------------------------
# executor integration: parity + honest trace content
# ---------------------------------------------------------------------------

def test_scan_with_recorder_is_bit_identical_and_traced(tmp_path):
    """The acceptance bar: attaching a Recorder to the tap transport
    changes NOTHING computed (bitwise state + curves) while the trace
    tells the true dispatch story (launch spans == launches, tap_round
    instants == rounds) and both exports validate."""
    job = _job()
    spec = _spec(job, T=6)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    plain = PlanExecutor(tr, plan, donate=False).run_scan(
        tr.init_state(jax.random.PRNGKey(0)), rounds_per_launch=4,
        metrics="tap")
    rec = Recorder()
    ex = PlanExecutor(tr, plan, donate=False, recorder=rec)
    res = ex.run_scan(tr.init_state(jax.random.PRNGKey(0)),
                      rounds_per_launch=4, metrics="tap")
    _assert_states_equal(plain.state, res.state)
    for k, v in plain.metrics.items():
        np.testing.assert_array_equal(v, res.metrics[k])

    counters = rec.tracer.counters()
    assert counters["rounds"] == 6
    assert counters["launches"] == res.stats.launches == 2
    assert counters["tap_events"] == res.stats.tap_events == 6
    assert counters["host_syncs"] == res.stats.host_syncs == 0
    phases = rec.tracer.phase_table()
    assert phases["launch"]["count"] == 2
    taps = [e for e in rec.tracer.chrome_trace()["traceEvents"]
            if e.get("name") == "tap_round"]
    assert len(taps) == 6 and all(e["ph"] == "i" for e in taps)
    # the retrace sentinel saw the warm-up compiles
    assert ex.compile_counts()["chunk[tap]"] >= 1
    assert counters["compiles"] >= 1

    trace = json.load(open(rec.export_chrome(str(tmp_path / "t.json"))))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"launch", "barrier", "tap_round"} <= names
    validate_metrics_log(rec.export_metrics(str(tmp_path / "m.jsonl")))


def test_chunk_transport_records_host_syncs():
    job = _job()
    spec = _spec(job, T=6)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    rec = Recorder()
    # an on_step forces the per-chunk readback (without it the transport
    # defers to ONE end-of-run sync — also worth asserting)
    res = PlanExecutor(tr, plan, donate=False, recorder=rec).run_scan(
        tr.init_state(jax.random.PRNGKey(0)), rounds_per_launch=3,
        metrics="chunk", on_step=lambda i, st, m: None)
    c = rec.tracer.counters()
    assert c["host_syncs"] == res.stats.host_syncs == 2
    assert rec.tracer.phase_table()["host_sync"]["count"] == 2

    rec2 = Recorder()
    res2 = PlanExecutor(tr, plan, donate=False, recorder=rec2).run_scan(
        tr.init_state(jax.random.PRNGKey(0)), rounds_per_launch=3,
        metrics="chunk")
    assert rec2.tracer.counters()["host_syncs"] == res2.stats.host_syncs == 1
    syncs = rec2.tracer.phase_table()["host_sync"]
    assert syncs["count"] == 1


def test_eager_runtime_traces_per_round():
    job = _job()
    spec = _spec(job, T=4)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    rec = Recorder()
    res = PlanExecutor(tr, plan, donate=False, recorder=rec).run_eager(
        tr.init_state(jax.random.PRNGKey(0)))
    c = rec.tracer.counters()
    assert c["rounds"] == 4
    assert c["launches"] == res.stats.launches == 4
    assert rec.tracer.phase_table()["launch"]["count"] == 4


# ---------------------------------------------------------------------------
# snapshot + server integration
# ---------------------------------------------------------------------------

def test_snapshot_spans_show_async_overlap(tmp_path):
    from repro.checkpoint import AsyncSnapshotter

    job = _job()
    spec = _spec(job, T=8)
    plan = _plan_for(spec, job)
    tr = _trainer(job)
    rec = Recorder()
    snap = AsyncSnapshotter(str(tmp_path / "snaps"), 4, meta={"arch": "t"})
    res = PlanExecutor(tr, plan, donate=False, recorder=rec).run_scan(
        tr.init_state(jax.random.PRNGKey(0)), rounds_per_launch=4,
        metrics="tap", snapshot=snap)
    assert res.stats.snapshots == 2
    c = rec.tracer.counters()
    assert c["snapshots"] == 2
    assert c["snapshot_writes"] == 2                 # drained by run end
    phases = rec.tracer.phase_table()
    assert phases["snapshot_offer"]["count"] == 2
    assert phases["snapshot_copy"]["count"] == 2
    assert phases["snapshot_finalise"]["count"] == 2


def test_slot_server_trace_tells_admission_story(tmp_path):
    from repro.configs import get_arch
    from repro.distributed import SlotConfig, SlotServer
    from repro.models import init_params
    from jax.sharding import Mesh

    cfg = get_arch("qwen2-0.5b").reduced().with_(
        remat="none", n_layers=1, d_model=8, n_heads=1, n_kv_heads=1,
        d_ff=16, vocab=127)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (5, 5)).astype(np.int32)

    rec = Recorder()
    srv = SlotServer(cfg, mesh, SlotConfig(n_slots=2, ctx_len=16,
                                           steps_per_launch=2),
                     recorder=rec)
    plain = SlotServer(cfg, mesh, SlotConfig(n_slots=2, ctx_len=16,
                                             steps_per_launch=2))
    arrivals = np.array([0, 0, 1, 3, 6])
    res = srv.serve(params, prompts, 6, admission="shuffled",
                    arrivals=arrivals)
    ref = plain.serve(params, prompts, 6, admission="shuffled",
                      arrivals=arrivals)
    np.testing.assert_array_equal(ref.tokens, res.tokens)  # obs is inert

    # the retrace gate's registry shape survived the CompileWatch move
    counts = srv.compile_counts()
    assert counts["chunk"] == 1 and counts["admit"] == 1
    assert counts["prefill[5]"] == 1
    c = rec.tracer.counters()
    assert c["requests"] == 5
    assert c["completions"] == 5
    phases = rec.tracer.phase_table()
    assert phases["admit"]["count"] == 5
    assert phases["prefill"]["count"] == 5
    assert phases["request"]["count"] == 5           # one span per rid
    assert phases["tap"]["count"] == res.tap_rows    # one per decode step
    trace = json.load(open(rec.export_chrome(str(tmp_path / "s.json"))))
    # the sweep is a context span: it holds the prefills and admits it
    # dispatches (1e-3 µs of slack for the ns -> µs division)
    sweeps = [(e["ts"], e["ts"] + e["dur"]) for e in trace["traceEvents"]
              if e.get("name") == "admission_sweep"]
    inner = [e for e in trace["traceEvents"]
             if e.get("name") in ("prefill", "admit")]
    assert len(inner) == 10
    assert all(any(a - 1e-3 <= e["ts"] and e["ts"] + e["dur"] <= b + 1e-3
                   for a, b in sweeps) for e in inner)
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"server", "slot0", "slot1"} <= lanes
    assert "ttft_steps" in rec.tracer.hist_summaries()


# ---------------------------------------------------------------------------
# the summary surface
# ---------------------------------------------------------------------------

def test_obs_summary_survives_runresult_json():
    job = _job()
    spec = _spec(job, T=6, runtime="scan", rounds_per_launch=3,
                 metrics="tap")
    rec = Recorder()
    backend = TrainerBackend(
        mesh=None, recorder=rec)
    res = backend.run(spec)
    obs = res.extra["obs"]
    assert obs["schema_version"] == METRICS_SCHEMA_VERSION
    assert obs["counters"]["rounds"] == 6
    restored = RunResult.from_json(res.to_json())
    assert restored.extra["obs"]["counters"] == obs["counters"]
    text = render_summary(restored.extra["obs"], trace=restored.trace)
    assert "launch" in text and "rounds/s" in text
    assert "tau_max" in text
    # satellite: breaker/snapshot state surfaced next to obs
    assert "tripped_round" in res.extra


def test_render_summary_handles_empty():
    assert "(no spans recorded)" in render_summary({"wall_s": 0.0})
