"""A whole benchmark run at a tiny size on the CPU (past the look for a
chip), once sound and once with the timed path broken underneath: each
fault a cell can have must turn ``correct`` false.  The limits are the
tiny stand-ins' own (``tiny.LIMITS``), set by the cells' rule."""
import json

import jax.numpy as jnp
import pytest

import tiny
from bench import harness, run

SEED = "2147483651"


def _run(capsys, monkeypatch, workload, overrides):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    rc = run.main(["--workload", workload, "--seed", SEED, "--seconds", "1",
                   "--trace", "0"], require_chip=False, overrides=overrides)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


def _train(capsys, monkeypatch, config=tiny.DENSE,
           workload="train.qwen2-0.5b.shuffled"):
    return _run(capsys, monkeypatch, workload,
                {"config": config, "mix": tiny.train_mix(),
                 "limits": tiny.LIMITS[workload]})


def _state_unchanged(monkeypatch):
    from repro.distributed.async_trainer import AsyncTrainer

    orig = AsyncTrainer.train_step_fn

    def train_step_fn(self):
        step = orig(self)

        def stuck(state, *a, **k):
            _, m = step(state, *a, **k)
            return state, m
        return stuck
    monkeypatch.setattr(AsyncTrainer, "train_step_fn", train_step_fn)


def _half_batch(monkeypatch):
    from repro.distributed.async_trainer import AsyncTrainer

    orig = AsyncTrainer._example_weights

    def weights(self, mask, batch_size):
        per = batch_size // self.n_groups
        keep = (jnp.arange(batch_size) % per) < max(per // 2, 1)
        return orig(self, mask, batch_size) * keep
    monkeypatch.setattr(AsyncTrainer, "_example_weights", weights)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_train_run_sees_each_fault(capsys, monkeypatch, fault):
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch)
    elif fault == "half_batch":
        _half_batch(monkeypatch)
    line = _train(capsys, monkeypatch)
    assert line["correct"] is (fault is None), line["checks"]


def test_serve_run_sees_an_altered_token(capsys, monkeypatch):
    from repro.distributed import slot_serve

    cell = "serve.qwen2-0.5b.saturated"
    over = {"config": tiny.DENSE, "mix": tiny.serve_mix(),
            "limits": tiny.LIMITS[cell]}
    line = _run(capsys, monkeypatch, cell, over)
    assert line["correct"] is True, line["checks"]
    orig = slot_serve.M.decode_step

    def altered(cfg, params, cache, tokens, pos, ctx_len):
        logits, cache = orig(cfg, params, cache, tokens, pos, ctx_len)
        return logits.at[:, 7].set(jnp.max(logits, -1) + 1.0), cache
    monkeypatch.setattr(slot_serve.M, "decode_step", altered)
    line = _run(capsys, monkeypatch, cell, over)
    assert line["correct"] is False, line["checks"]
