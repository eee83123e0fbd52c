"""ServeBackend edge cases: a one-token budget (only the prefill token, no
decode step launched), a batch of one prompt (one slot, whose lane
sharding is replicated), greedy serving that repeats across calls, and
the objective type check.
"""
import numpy as np
import jax
import pytest
from jax.sharding import Mesh

from repro.api import ExperimentSpec, ServeBackend, ServeJob, run
from repro.configs import get_arch
from repro.distributed import SlotConfig, SlotServer
from repro.models import init_params


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_serve_backend_single_decode_step():
    """spec.T == 1: only the prefill token is emitted (no decode chunk
    runs) — exactly (batch, 1), finite throughput stats."""
    res = ServeBackend(mesh=_mesh()).run(ExperimentSpec(
        scheduler="pure", objective=ServeJob(batch=2, prompt_len=4), T=1,
        n_workers=2, seed=0))
    assert res.x.shape == (2, 1)
    assert res.extra["prompts"].shape == (2, 4)
    assert res.extra["chunks"] == 0 and res.extra["decode_steps"] == 0
    assert np.isfinite(res.extra["tok_per_s"])


def test_serve_backend_batch_of_one():
    res = run(ExperimentSpec(
        scheduler="pure", objective=ServeJob(batch=1, prompt_len=3), T=3,
        n_workers=1, seed=1))
    assert res.backend == "serve"
    assert res.x.shape == (1, 3)
    assert res.extra["n_slots"] == 1


def test_slot_serve_greedy_repeats_across_calls():
    """Greedy serving is a pure function of the prompts: a second call on
    the same server gives the same tokens and compiles nothing new."""
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    params = init_params(cfg, jax.random.PRNGKey(0))
    srv = SlotServer(cfg, _mesh(), SlotConfig(n_slots=2, ctx_len=12,
                                              steps_per_launch=2))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (3, 4)).astype(np.int32)
    a = srv.serve(params, prompts, 5)
    counts = dict(srv.compile_counts())
    b = srv.serve(params, prompts, 5)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert srv.compile_counts() == counts


def test_serve_backend_rejects_wrong_objective():
    with pytest.raises(TypeError, match="ServeJob"):
        ServeBackend(mesh=_mesh()).run(
            ExperimentSpec(scheduler="pure", objective=None, n_workers=2,
                           T=2))
