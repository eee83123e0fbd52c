"""The control of each cell, kept as a test at a size a test run holds:
the reference put in the program's place and computed in float8 (per-
tensor scaled, the precision below the configurations' bfloat16) must
fail at least one of the cell's numbers against the float32 reference,
under the limits of the tiny stand-ins (``tiny.LIMITS``, set by the
cells' rule at this size).  The chip's readings at the cells' own size
are in PERF.md."""
import numpy as np
import pytest

import tiny
from bench.drivers.train import compare
from bench.reference import follow

SEEDS = [2147483659, 11, 2024]


@pytest.mark.parametrize("cell,cfg,mix", [
    ("train.qwen2-0.5b.shuffled", tiny.DENSE, "train.shuffled"),
    ("train.mamba2-370m.shuffled", tiny.SSM, "train.shuffled"),
    ("train.qwen2-0.5b.waiting", tiny.DENSE, "train.waiting"),
], ids=["qwen2-shuffled", "mamba2-shuffled", "qwen2-waiting"])
@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails_a_number(cell, cfg, mix, seed):
    m = tiny.train_mix(mix)
    ref = follow.train_steps(cfg, m, seed, 4)
    low = follow.train_steps(cfg, m, seed, 4, precision="fp8")
    checks, readings, _ = compare(low, ref, tiny.LIMITS[cell])
    assert any(c["value"] > c["limit"] for c in checks.values()), readings


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails_the_logit_gap(seed):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, tiny.DENSE["vocab_size"], (2, 16))
    served = rng.integers(0, tiny.DENSE["vocab_size"], (2, 16))
    low = follow.serve_gaps(tiny.DENSE, seed, prompts, served,
                            precision="fp8")
    limit = tiny.LIMITS["serve.qwen2-0.5b.saturated"]["logit_gap"]
    assert float(np.max(low)) > limit
