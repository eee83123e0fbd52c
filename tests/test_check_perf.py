"""Unit coverage for the benchmarks/check_perf.py CI gate.

The regression under test: a baseline row carrying ``grid_speedup`` whose
*current* row lacks the field used to read ``cur.get("grid_speedup",
0.0)`` and fail with a bogus ``0.000 < floor`` REGRESSION verdict — the
failure message must say the FIELD is missing, not that throughput
dropped to zero.  Plus the other kinds' compare paths and the
kind-dispatch rules.
"""
import importlib.util
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "benchmarks" / "check_perf.py"

_spec = importlib.util.spec_from_file_location("check_perf", SCRIPT)
check_perf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_perf)


def _runtime_payload(*, grid_speedup=None, rounds_per_s=100.0):
    entry = {"runtime": "scan", "metrics": "chunk", "rounds_per_launch": 8,
             "rounds_per_s": rounds_per_s}
    if grid_speedup is not None:
        entry["grid_speedup"] = grid_speedup
    return {"bench": "runtime_dispatch_ab",
            "entries": [{"runtime": "eager", "metrics": "chunk",
                         "rounds_per_launch": 1, "rounds_per_s": 50.0},
                        entry]}


# ---------------------------------------------------------------------------
# the missing-field regression (satellite bugfix)
# ---------------------------------------------------------------------------

def test_missing_grid_speedup_reports_missing_not_zero(capsys):
    base = _runtime_payload(grid_speedup=3.0)
    cur = _runtime_payload()                 # field vanished from current
    failures = check_perf.check_runtime(cur, base, tolerance=0.3)
    assert len(failures) == 1
    assert "lacks the field" in failures[0]
    # the old bug compared 0.0 against the floor and printed "0.000 <"
    assert "0.000" not in failures[0]
    assert "MISSING" in capsys.readouterr().out


def test_present_grid_speedup_still_gated():
    base = _runtime_payload(grid_speedup=3.0)
    ok = check_perf.check_runtime(_runtime_payload(grid_speedup=2.9),
                                  base, tolerance=0.3)
    assert ok == []
    bad = check_perf.check_runtime(_runtime_payload(grid_speedup=1.0),
                                   base, tolerance=0.3)
    assert len(bad) == 1 and "grid_speedup" in bad[0]


def test_rows_returns_rows_and_eager_tuple():
    rows, eager = check_perf._rows(_runtime_payload())
    assert eager == 50.0
    assert ("scan", "chunk", 8) in rows


# ---------------------------------------------------------------------------
# the obs kind (absolute ceiling, like faults)
# ---------------------------------------------------------------------------

def _obs_payload(*, ratio=0.99, trace=True, metrics=True, taps=True):
    return {"bench": "obs", "overhead_ratio": ratio, "trace_valid": trace,
            "metrics_valid": metrics, "tap_events_match": taps}


def test_obs_kind_gates_absolute_ceiling():
    base = _obs_payload(ratio=0.99)
    assert check_perf.check_obs(_obs_payload(ratio=0.97), base,
                                tolerance=0.05) == []
    fails = check_perf.check_obs(_obs_payload(ratio=0.90), base,
                                 tolerance=0.05)
    assert len(fails) == 1 and "overhead_ratio" in fails[0]
    # the ceiling is absolute: a degraded committed baseline must NOT
    # grandfather a current ratio below 1 - tolerance
    fails = check_perf.check_obs(_obs_payload(ratio=0.90),
                                 _obs_payload(ratio=0.89), tolerance=0.05)
    assert len(fails) == 1


def test_obs_kind_gates_structural_flags():
    base = _obs_payload()
    for kw, name in ((dict(trace=False), "trace_valid"),
                     (dict(metrics=False), "metrics_valid"),
                     (dict(taps=False), "tap_events_match")):
        fails = check_perf.check_obs(_obs_payload(**kw), base,
                                     tolerance=0.05)
        assert len(fails) == 1 and name in fails[0]


def test_obs_kind_reports_payload_shape_change_with_file_name():
    fails = check_perf.check_obs({"bench": "obs"}, _obs_payload(),
                                 tolerance=0.05,
                                 paths=("cur_obs.json", "base_obs.json"))
    # the missing ratio no longer short-circuits: the flag rows (also
    # failing on an empty payload) are reported alongside it
    assert len(fails) == 4
    assert "overhead_ratio" in fails[0] and "cur_obs.json" in fails[0]
    assert any("trace_valid" in f for f in fails[1:])


# ---------------------------------------------------------------------------
# file names in SKIP / FAILURE messages (satellite bugfix)
# ---------------------------------------------------------------------------

def test_missing_row_failure_names_both_files():
    base = _runtime_payload()
    cur = {"bench": "runtime_dispatch_ab",
           "entries": [{"runtime": "eager", "metrics": "chunk",
                        "rounds_per_launch": 1, "rounds_per_s": 50.0}]}
    fails = check_perf.check_runtime(cur, base, tolerance=0.3,
                                     paths=("cur.json", "base.json"))
    assert len(fails) == 1
    assert "cur.json" in fails[0] and "base.json" in fails[0]


def test_rows_without_eager_names_the_file():
    with pytest.raises(SystemExit, match="weird.json"):
        check_perf._rows({"entries": []}, "weird.json")


def test_faults_kind_missing_ratio_is_clean_failure_not_keyerror():
    fails = check_perf.check_faults({"bench": "faults"}, {},
                                    tolerance=0.1,
                                    paths=("cur_faults.json", "b.json"))
    # every failing row of the file is reported, not just the first
    assert len(fails) == 4 and "cur_faults.json" in fails[0]
    assert any("unguarded_poisoned" in f for f in fails[1:])


# ---------------------------------------------------------------------------
# resilience kind (absolute ceiling + accounting flags)
# ---------------------------------------------------------------------------

def _resilience_payload(ratio=1.02, identical=True, accounted=True):
    return {"bench": "resilience", "retry_overhead_ratio": ratio,
            "clean_token_identical": identical, "all_accounted": accounted}


def test_resilience_kind_passes_and_ceiling_is_absolute():
    base = _resilience_payload(ratio=1.5)    # baseline never relaxes it
    assert check_perf.check_resilience(_resilience_payload(ratio=0.95),
                                       base, tolerance=0.1) == []
    fails = check_perf.check_resilience(_resilience_payload(ratio=0.85),
                                        base, tolerance=0.1)
    assert len(fails) == 1 and "retry_overhead_ratio" in fails[0]


def test_resilience_kind_gates_accounting_flags():
    base = _resilience_payload()
    for kw, name in ((dict(identical=False), "clean_token_identical"),
                     (dict(accounted=False), "all_accounted")):
        fails = check_perf.check_resilience(_resilience_payload(**kw),
                                            base, tolerance=0.1)
        assert len(fails) == 1 and name in fails[0]


def test_resilience_kind_missing_ratio_reports_all_rows():
    fails = check_perf.check_resilience({"bench": "resilience"},
                                        _resilience_payload(),
                                        tolerance=0.1,
                                        paths=("cur_r.json", "b.json"))
    assert len(fails) == 3 and "cur_r.json" in fails[0]


# ---------------------------------------------------------------------------
# kind dispatch through main()
# ---------------------------------------------------------------------------

def _run_main(tmp_path, cur, base, extra=()):
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    return subprocess.run(
        [sys.executable, str(SCRIPT), str(cur_p), str(base_p), *extra],
        capture_output=True, text=True)


def test_main_skips_unknown_kind(tmp_path):
    r = _run_main(tmp_path, {"bench": "scenarios", "entries": []},
                  _runtime_payload())
    assert r.returncode == 0
    assert "SKIP" in r.stdout


def test_main_rejects_kind_mismatch(tmp_path):
    r = _run_main(tmp_path, _obs_payload(), _runtime_payload())
    assert r.returncode != 0
    out = r.stdout + r.stderr
    assert "mismatch" in out
    # both offending files are named
    assert "cur.json" in out and "base.json" in out


def test_main_accepts_obs_payload(tmp_path):
    r = _run_main(tmp_path, _obs_payload(), _obs_payload(),
                  extra=("--tolerance", "0.05"))
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_main(tmp_path, _obs_payload(ratio=0.5), _obs_payload(),
                  extra=("--tolerance", "0.05"))
    assert r.returncode == 1
    assert "PERF REGRESSION" in r.stdout
