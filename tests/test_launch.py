"""Launch path: HLO cost model unit tests + a real dry-run in a subprocess
(the 512-device XLA flag must be set before jax init, hence the subprocess)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import enable_compile_cache
from repro.launch.hlo_cost import analyze, parse_module, _split_instr

HLO = """\
HloModule test

%region_body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot), replica_groups=[2,4]<=[8], to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ni, %ar)
}

%region_cond (q: (s32[], f32[8,16])) -> pred[] {
  %q = (s32[], f32[8,16]) parameter(0)
  %j = s32[] get-tuple-element(%q), index=0
  %lim = s32[] constant(5)
  ROOT %lt = pred[] compare(%j, %lim), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %tup = (s32[], f32[8,16]) tuple(%zero, %a)
  %wh = (s32[], f32[8,16]) while(%tup), condition=%region_cond, body=%region_body
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_split_instr_handles_tuples_and_comments():
    got = _split_instr("  %wh.1 = (s32[], /*index=1*/f32[2,3]{1,0}) "
                       "while(%tup), condition=%c, body=%b")
    assert got is not None
    name, ty, opcode, operands, attrs = got
    assert name == "wh.1" and opcode == "while"
    assert "condition=%c" in attrs and "body=%b" in attrs
    got2 = _split_instr("  %ar = f32[4]{0} all-reduce(%x), "
                        "replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add")
    assert got2[2] == "all-reduce"
    assert "to_apply=%add" in got2[4]


def test_analyze_counts_loop_trips():
    r = analyze(HLO)
    assert r.n_while == 1 and r.unknown_trip_loops == 0
    # dot: 2*8*16*16 = 4096 flops × 5 trips
    assert r.dot_flops == 5 * 2 * 8 * 16 * 16
    # all-reduce operand: 8*16*4 bytes × 5 trips
    assert r.collective_bytes == 5 * 8 * 16 * 4
    assert r.collective_breakdown["all-reduce"] == r.collective_bytes


def test_parse_module_symbol_table():
    comps, entry, symbols = parse_module(HLO)
    assert entry == "main"
    assert "region_body" in comps and "region_cond" in comps
    assert symbols["dot"].startswith("f32[8,16]")


@pytest.mark.slow
def test_dryrun_subprocess_end_to_end(tmp_path):
    """Lower+compile one real (arch × shape × production-mesh) combo."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-0.5b",
         "--shape", "decode_32k", "--out", out],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.load(open(os.path.join(out, "qwen2-0.5b_decode_32k_sp.json")))
    assert rec["ok"]
    assert rec["n_devices"] == 256
    assert rec["hlo_cost"]["dot_flops"] > 0
    assert rec["memory"]["peak_bytes_est"] < 16e9


def test_roofline_analysis_on_existing_records():
    """If the sweep artifacts exist, every single-pod record must be ok and
    produce finite roofline terms."""
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "experiments", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("no dry-run artifacts yet")
    from repro.launch.roofline import load_table
    rows = load_table(d, "sp")
    assert rows
    for r in rows:
        assert "error" not in r, r
        assert r["compute_s"] > 0 and r["memory_s"] > 0
        assert r["dominant"] in ("compute", "memory", "collective")


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_leaves_the_environment_setting_to_jax(monkeypatch,
                                                            tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
