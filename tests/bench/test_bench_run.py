"""``bench/run.py`` refuses to run where it cannot measure: with no TPU,
and in a checkout that holds the benchmark's files but not the program."""
import json
import os
import shutil
import subprocess
import sys

from tiny import ROOT

CELL = "serve.qwen2-0.5b.saturated"


ARGS = ["--workload", CELL, "--seed", "2147483700", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None, require_chip=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    cmd = [sys.executable, "bench/run.py"] + ARGS
    if not require_chip:
        cmd = [sys.executable, "-c",
               "import sys; sys.path.insert(0, '.'); from bench import run; "
               f"sys.exit(run.main({ARGS!r}, require_chip=False))"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "needs 1 TPU" in p.stderr
    assert _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    with open(ROOT / "BENCHMARK.json") as f:
        paths = json.load(f)["paths"]
    for p in paths:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result(p.stdout)
    # past the look for a chip, the missing program stops the run too
    p = _run(tmp_path, {"PYTHONPATH": ""}, require_chip=False)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert _no_result(p.stdout)
