"""The readers of the program's own spans and compile log, on a
hand-built reduced trace: device 0 busy in [0, 100], [300, 600] and
[900, 1000] of a window [0, 1000] ns, so idle in [100, 300] and
[600, 900]; ``server.admission_sweep`` on the main thread and
``server.tap`` on the callback thread."""
import json
from typing import NamedTuple

import pytest

import repro.obs
import tiny
from bench import harness, run

SWEEPS = [(-100, 20), (50, 250), (550, 700), (950, 1100)]
TAPS = [(200, 350), (880, 920)]


def _trace(host):
    return {"window_ns": (0, 1000), "window_s": 1e-6, "n_devices": 1,
            "busy": [[[0, 100], [300, 600], [900, 1000]]], "host": host}


def _host(sweeps=SWEEPS, taps=TAPS):
    return ([(s, e, "server.admission_sweep", "python") for s, e in sweeps]
            + [(s, e, "server.tap", "callback") for s, e in taps]
            + [(0, 1000, "serve", "python")])


@pytest.mark.parametrize("trace, want", [
    # taps idle 100 + 20
    (_trace(_host()), 12.0),
    # a tap that reaches past the window counts only inside it
    (_trace(_host(taps=[(880, 1200)])), 2.0),
    (_trace(_host(taps=[])), None),
    (None, None),
])
def test_span_readers(trace, want):
    read = harness.metric_reader("tap_idle_share.serve")
    got = read({}, trace)
    assert got == (pytest.approx(want) if want is not None else None)


def test_idle_shares_stay_inside_the_idle_time():
    tr = _trace(_host(taps=TAPS + [(100, 300), (600, 900)]))
    tap = harness.metric_reader("tap_idle_share.serve")({}, tr)
    idle = harness.metric_reader("device_idle_share.serve")(
        {}, dict(tr, busy_s=0.5e-6))
    assert tap == pytest.approx(idle)        # every idle ns under a tap


class E(NamedTuple):
    """The fields of ``repro.obs.compile_log()``'s entries."""
    program: object
    fun_name: str
    phase: str
    start_s: float
    end_s: float
    cache_hit: bool


def test_setup_compile_s_is_the_union_of_watched_phases(monkeypatch):
    log = [E("chunk", "body", "trace", 10.0, 10.5, False),
           E("chunk", "chunk", "trace", 9.8, 11.0, False),   # holds the first
           E("chunk", "jit(chunk)", "lower", 11.0, 11.5, False),
           E("chunk", "jit(chunk)", "backend", 11.5, 14.0, True),
           E(None, "init", "backend", 2.0, 8.0, False),     # unwatched
           E("admit", "jit(admit)", "backend", 20.0, 21.0, False)]
    read = harness.metric_reader("setup_compile_s")
    monkeypatch.setattr(repro.obs, "compile_log", lambda: log,
                        raising=False)
    assert read({}, None) == pytest.approx(4.2 + 1.0)
    monkeypatch.setattr(repro.obs, "compile_log", lambda: log[4:5])
    assert read({}, None) is None
    monkeypatch.delattr(repro.obs, "compile_log")     # a program without it
    assert read({}, None) is None


def test_traced_tiny_serve_run_reads_the_program(capsys, monkeypatch):
    """A whole traced run at the tiny size on the CPU: the program's
    annotations and compile log reach the readers (the CPU has no device
    plane, so the tap's idle share stays silent there)."""
    cell = "serve.qwen2-0.5b.saturated"
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    seen, reader = {}, harness.metric_reader

    def spy(name):
        read = reader(name)
        return lambda rec, tr: seen.update(tr or {}) or read(rec, tr)
    monkeypatch.setattr(harness, "metric_reader", spy)
    rc = run.main(["--workload", cell, "--seed", "2147483652", "--seconds",
                   "1", "--trace", "1"], require_chip=False,
                  overrides={"config": tiny.DENSE, "mix": tiny.serve_mix(),
                             "limits": tiny.LIMITS[cell]})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metrics"]["setup_compile_s"]["value"] > 0
    names = {name for _, _, name, _ in seen["host"]}
    assert {"server.admission_sweep", "server.prefill", "server.admit",
            "server.launch", "server.tap"} <= names
