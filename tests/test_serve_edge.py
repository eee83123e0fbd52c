"""ServeBackend / Server.generate edge cases (PR-1 followups).

The decode driver has two boundary behaviours that previously had no
dedicated assertions: ``n_steps <= 0`` (must return an empty (B, 0) array
WITHOUT compiling or stepping anything) and a batch of one prompt (the
token sharding switches to replicated when batch == 1).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.api import ExperimentSpec, ServeBackend, ServeJob, run
from repro.configs import get_arch
from repro.distributed import Server, ServeConfig
from repro.models import init_params


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _server(batch, ctx=24, temperature=0.0):
    cfg = get_arch("qwen2-0.5b").reduced().with_(remat="none")
    srv = Server(cfg, _mesh(), ServeConfig(batch=batch, ctx_len=ctx,
                                           temperature=temperature))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, srv, params


def test_generate_zero_steps_returns_empty():
    cfg, srv, params = _server(batch=2)
    prompts = np.array([3, 5], dtype=np.int32)
    for n_steps in (0, -1):
        out = srv.generate(params, prompts, n_steps)
        assert out.shape == (2, 0)
        assert out.dtype == np.int32


def test_generate_zero_steps_does_not_compile(monkeypatch):
    """The n_steps <= 0 early-out must not pay a jit compile (the whole
    point of the guard)."""
    cfg, srv, params = _server(batch=2)

    def boom(*a, **k):
        raise AssertionError("jit_serve_step must not be called")

    monkeypatch.setattr(srv, "jit_serve_step", boom)
    out = srv.generate(params, np.array([1, 2], dtype=np.int32), 0)
    assert out.shape == (2, 0)


def test_generate_batch_of_one_prompt():
    """batch == 1 flips the token sharding to replicated — the driver must
    still decode and keep shapes (1, n_steps)."""
    cfg, srv, params = _server(batch=1)
    out = srv.generate(params, np.array([7], dtype=np.int32), 4)
    assert out.shape == (1, 4)
    assert out.dtype == np.int32
    assert np.all((out >= 0) & (out < cfg.vocab))


def test_generate_greedy_is_deterministic():
    cfg, srv, params = _server(batch=1)
    a = srv.generate(params, np.array([7], dtype=np.int32), 3)
    b = srv.generate(params, np.array([7], dtype=np.int32), 3)
    np.testing.assert_array_equal(a, b)


def test_serve_backend_single_decode_step():
    """spec.T == 1: only the prefill token is emitted (generate runs for
    T − 1 = 0 steps) — exactly (batch, 1), finite throughput stats."""
    res = ServeBackend(mesh=_mesh()).run(ExperimentSpec(
        scheduler="pure", objective=ServeJob(batch=2, prompt_len=4), T=1,
        n_workers=2, seed=0))
    assert res.x.shape == (2, 1)
    assert res.extra["prompts"].shape == (2, 4)
    assert np.isfinite(res.extra["tok_per_s"])


def test_serve_backend_batch_of_one():
    res = run(ExperimentSpec(
        scheduler="pure", objective=ServeJob(batch=1, prompt_len=3), T=3,
        n_workers=1, seed=1))
    assert res.backend == "serve"
    assert res.x.shape == (1, 3)


def test_generate_compiles_once_across_calls():
    """Regression: jit_serve_step used to build a FRESH jax.jit wrapper per
    generate call, so every call retraced and recompiled the step.  The
    wrapper must now be cached on the instance, and a second generate must
    add ZERO backend compiles and ZERO traced signatures."""
    from jax import monitoring

    cfg, srv, params = _server(batch=2)
    compiles = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        prompts = np.array([3, 5], dtype=np.int32)
        srv.generate(params, prompts, 3)
        assert srv.jit_serve_step() is srv.jit_serve_step()
        n_compiles = len(compiles)
        n_traces = srv.jit_serve_step()._cache_size()
        srv.generate(params, prompts, 3)
        assert len(compiles) == n_compiles, \
            "second generate() recompiled the serve step"
        assert srv.jit_serve_step()._cache_size() == n_traces, \
            "second generate() retraced the serve step"
    finally:
        # only this listener: clearing them all would drop repro.obs's
        # compile listener for every later test in the process
        monitoring.unregister_event_duration_listener(listener)


def test_generate_threads_sampling_key_across_calls():
    """Regression: generate used to rebuild PRNGKey(seed) per call, so
    successive temperature-sampled calls replayed the SAME stream."""
    cfg, srv, params = _server(batch=2, temperature=1.0)
    prompts = np.array([3, 5], dtype=np.int32)
    a = srv.generate(params, prompts, 6)
    b = srv.generate(params, prompts, 6)
    assert not np.array_equal(a, b), \
        "two consecutive sampled calls replayed the same PRNG stream"


def test_generate_explicit_key_reproduces_without_consuming_stream():
    """A caller-supplied key gives reproducible draws and must not disturb
    the server's persistent stream."""
    cfg, srv, params = _server(batch=2, temperature=1.0)
    prompts = np.array([3, 5], dtype=np.int32)
    first = srv.generate(params, prompts, 4)
    k = jax.random.PRNGKey(7)
    e1 = srv.generate(params, prompts, 4, key=k)
    e2 = srv.generate(params, prompts, 4, key=k)
    np.testing.assert_array_equal(e1, e2)
    second = srv.generate(params, prompts, 4)
    # an identical fresh server draws the same first-then-second streams,
    # proving the explicit-key calls consumed nothing from the instance
    _, srv2, _ = _server(batch=2, temperature=1.0)
    np.testing.assert_array_equal(first, srv2.generate(params, prompts, 4))
    np.testing.assert_array_equal(second, srv2.generate(params, prompts, 4))


def test_serve_backend_rejects_wrong_objective():
    with pytest.raises(TypeError, match="ServeJob"):
        ServeBackend(mesh=_mesh()).run(
            ExperimentSpec(scheduler="pure", objective=None, n_workers=2,
                           T=2))
