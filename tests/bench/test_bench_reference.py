"""The plain references against the program at a tiny size, on the CPU:
the seed laws (weights, tokens, participation masks), the loss and its
gradients, the delayed Adam step, and prefill-then-decode logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import common as R
from bench.reference import dense, ssm
from tiny import DENSE, SSM, train_mix

CASES = [(DENSE, dense), (SSM, ssm)]
IDS = ["dense", "ssm"]


def _arch(cfg, **kw):
    from repro.configs import get_arch
    return get_arch(cfg["registry"]).reduced().with_(**kw)


@pytest.mark.parametrize("cfg,fam", CASES, ids=IDS)
def test_weights_law_matches_the_program(cfg, fam):
    from repro.models import init_params

    key = jax.random.PRNGKey(2 ** 31 + 17)
    prog = jax.jit(lambda k: init_params(_arch(cfg), k))(key)
    ref = jax.jit(lambda k: R.init_weights(fam.declare(cfg), k))(key)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(prog))
    flat_r = dict(jax.tree_util.tree_leaves_with_path(ref))
    assert set(map(jax.tree_util.keystr, flat_p)) == \
        set(map(jax.tree_util.keystr, flat_r))
    for k, v in flat_p.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(flat_r[k]), err_msg=str(k))


@pytest.mark.parametrize("scheduler", ["shuffled", "pure_waiting:b=4"])
@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_schedule_and_token_laws_match_the_plan(scheduler, seed):
    from repro.api import ExperimentSpec, TrainJob, TrainerBackend
    from repro.runtime import compile_plan, make_batch_fn

    mix = train_mix()
    job = TrainJob(arch="qwen2-0.5b", reduced=True, global_batch=8,
                   seq_len=16, delay_rounds=1)
    spec = ExperimentSpec(scheduler=scheduler, timing=mix["timing"], T=96,
                          n_workers=4, seed=seed, objective=job)
    world = TrainerBackend.world_for(spec, 4)
    plan = compile_plan(world.schedule, job, rounds=96, n_groups=4,
                        seed=seed)
    masks = R.round_masks(scheduler, 4, 96, seed, 6.0)
    np.testing.assert_array_equal(plan.masks, masks)
    batch_of = make_batch_fn(plan, job.make_arch())
    cdf = R.zipf_cdf(512)
    perms = R.group_perms(512, 4, seed)
    for q in (0, 5, 95):
        np.testing.assert_array_equal(
            np.asarray(batch_of(jnp.asarray(plan.data_keys[q]))["tokens"]),
            np.asarray(R.train_tokens(seed, q, cdf, perms, 8, 16)))


@pytest.mark.parametrize("cfg,fam", CASES, ids=IDS)
def test_loss_and_gradients_match_the_program(cfg, fam):
    from repro.models import model as M

    arch = _arch(cfg, dtype="float32", remat="none")
    key = jax.random.PRNGKey(3)
    params = R.init_weights(fam.declare(cfg), key)
    toks = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0,
                              cfg["vocab_size"])
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    def prog_loss(p):
        return M.loss_fn(arch, p, {"tokens": toks}, example_weights=w)[0]

    def ref_loss(p):
        tot, wsum = fam.loss_sums(cfg, R.make_einsum("f32"), p, toks, w)
        return tot / (wsum + 1e-6)

    lp, gp = jax.value_and_grad(prog_loss)(params)
    lr, gr = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for (k, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(gp),
                              jax.tree_util.tree_leaves_with_path(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(k))


def test_delayed_adam_matches_the_program():
    from repro.optim import OptConfig
    from repro.optim.optimizers import adam_init, reference_delayed_apply

    cfg = OptConfig(lr=1e-2, clip_norm=1.0)
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    grads = [jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape) * s, jnp.float32),
        params) for s in (0.3, 2.0, 0.5)]
    p_prog, p_ref = params, params
    o_prog = adam_init(params)
    gbuf = jax.tree_util.tree_map(jnp.zeros_like, params)
    o_ref = R.adam_init(params)
    for i, g in enumerate(grads):
        gate = 0.0 if i == 0 else 1.0
        p_prog, gbuf, o_prog, n_prog = reference_delayed_apply(
            g, gbuf, o_prog, p_prog, cfg, lr_scale=gate)
        p_ref, o_ref, n_ref = R.delayed_adam(p_ref, o_ref, g, lr=1e-2,
                                             clip_norm=1.0)
        np.testing.assert_allclose(n_prog, n_ref, rtol=1e-6)
        for k in params:
            np.testing.assert_allclose(p_prog[k], p_ref[k], rtol=1e-6,
                                       atol=1e-7)


@pytest.mark.parametrize("cfg,fam", CASES, ids=IDS)
def test_prefill_then_decode_match_the_full_forward(cfg, fam):
    from repro.models import model as M

    arch = _arch(cfg, dtype="float32")
    params = R.init_weights(fam.declare(cfg), jax.random.PRNGKey(5))
    plen, new, ctx = 16, 6, 32
    prompt = jax.random.randint(jax.random.PRNGKey(6), (1, plen), 0,
                                cfg["vocab_size"])
    last, cache = M.prefill(arch, params, {"tokens": prompt}, ctx_len=ctx)
    got, toks = [last], [int(jnp.argmax(last[0]))]
    for i in range(new - 1):
        lg, cache = M.decode_step(arch, params, cache,
                                  jnp.asarray([toks[-1]], jnp.int32),
                                  jnp.int32(plen + i), ctx)
        got.append(lg)
        toks.append(int(jnp.argmax(lg[0])))
    seq = jnp.concatenate([prompt, jnp.asarray([toks[:-1]], jnp.int32)], 1)
    ref = fam.logits(cfg, R.make_einsum("f32"), params, seq, plen - 1,
                     plen - 1 + new)[0]
    np.testing.assert_allclose(np.concatenate([np.asarray(g) for g in got]),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)
