"""The granite-4.0-h-micro cell at a tiny size on the CPU: one whole
period of its layer pattern (MMMMMAMMMM) at d_model 128, with NoPE and
every multiplier kept, against the plain reference
(``bench/reference/hybrid.py``); its counts by hand; the registry against
the configuration file; and the reader of ``ssm_state_share.serve``."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import harness, run
from bench.counts import hybrid as counts
from bench.reference import common as R
from bench.reference import follow, hybrid

CELL = "serve.granite-4.0-h-micro.saturated"
SEED = "2147483653"

#: the registry's smoke variant of granite-4.0-h-micro, under the release's
#: keys and the keys ``check_widths`` reads
HYBRID = {
    "name": "granite-4.0-h-micro", "registry": "granite-4.0-h-micro",
    "registry_reduced": True, "family": "hybrid",
    "hidden_size": 128, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "mamba_d_state": 32, "mamba_d_head": 32, "mamba_n_heads": 8,
    "mamba_expand": 2, "mamba_d_conv": 4, "mamba_n_groups": 1,
    "mamba_chunk_size": 16, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8,
    "d_model": 128, "n_layer": 10, "tie_embeddings": True,
    "ssm_cfg": {"d_state": 32, "d_conv": 4, "expand": 2, "headdim": 32,
                "ngroups": 1, "chunk_size": 16},
}
#: the same at the release's vocabulary: at 512 rows the tied head returns
#: the input token at nearly every position (the multiplied embedding is
#: drawn at 1/√512), so no precision moves a served token; at 100,352 it
#: does not, as at full size
FULL_VOCAB = dict(HYBRID, vocab_size=100_352)
#: the limit at this size, set by the cells' rule from CPU readings at the
#: full vocabulary on six seeds: sound bfloat16 serving read logit_gap ≤
#: 1.4e-4, the float8 control ≥ 8.5e-4 (5–14 of 32 positions moved)
LIMITS = {"logit_gap": 2.5e-4}


def _cfg(name="granite-4.0-h-micro"):
    with open(tiny.ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def _arch(**kw):
    from repro.configs import get_arch
    return get_arch("granite-4.0-h-micro").reduced().with_(**kw)


def _serve_mix():
    return tiny.mix("serve.saturated.s64", n_slots=8, prompt_len=16,
                    max_new=16, arrival_gap_steps=2, ramp_steps=16,
                    warmup_requests=8, warmup_new=8, sample_requests=2,
                    window_margin=3.0)


# ------------------------------------------------------------ the program
def test_weights_law_matches_the_program():
    from repro.models import init_params

    key = jax.random.PRNGKey(2 ** 31 + 19)
    prog = jax.jit(lambda k: init_params(_arch(), k))(key)
    ref = jax.jit(lambda k: R.init_weights(hybrid.declare(HYBRID), k))(key)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(prog))
    flat_r = dict(jax.tree_util.tree_leaves_with_path(ref))
    assert set(map(jax.tree_util.keystr, flat_p)) == \
        set(map(jax.tree_util.keystr, flat_r))
    for k, v in flat_p.items():
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(flat_r[k]), err_msg=str(k))


def test_loss_and_gradients_match_the_program():
    from repro.models import model as M

    arch = _arch(dtype="float32", remat="none")
    params = R.init_weights(hybrid.declare(HYBRID), jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0, 512)
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    def prog_loss(p):
        return M.loss_fn(arch, p, {"tokens": toks}, example_weights=w)[0]

    def ref_loss(p):
        tot, wsum = hybrid.loss_sums(HYBRID, R.make_einsum("f32"), p, toks, w)
        return tot / (wsum + 1e-6)

    lp, gp = jax.value_and_grad(prog_loss)(params)
    lr, gr = jax.value_and_grad(ref_loss)(params)
    # both float32: the loss differs only by summation order
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    # gradients pass back through ten layers and two scans (the program's
    # chunked SSD, the reference's step-by-step recurrence), whose sums run
    # in different orders; atol covers leaves near zero
    for (k, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(gp),
                              jax.tree_util.tree_leaves_with_path(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(k))


def test_prefill_then_decode_match_the_full_forward():
    from repro.models import model as M

    arch = _arch(dtype="float32")
    params = R.init_weights(hybrid.declare(HYBRID), jax.random.PRNGKey(5))
    plen, new, ctx = 16, 6, 32
    prompt = jax.random.randint(jax.random.PRNGKey(6), (1, plen), 0, 512)
    last, cache = M.prefill(arch, params, {"tokens": prompt}, ctx_len=ctx)
    got, toks = [last], [int(jnp.argmax(last[0]))]
    for i in range(new - 1):
        lg, cache = M.decode_step(arch, params, cache,
                                  jnp.asarray([toks[-1]], jnp.int32),
                                  jnp.int32(plen + i), ctx)
        got.append(lg)
        toks.append(int(jnp.argmax(lg[0])))
    seq = jnp.concatenate([prompt, jnp.asarray([toks[:-1]], jnp.int32)], 1)
    ref = hybrid.logits(HYBRID, R.make_einsum("f32"), params, seq, plen - 1,
                        plen - 1 + new)[0]
    # float32 on both sides; the chunked scan and the recurrence sum in
    # different orders (logits are ~1e-1 after the / 8)
    np.testing.assert_allclose(np.concatenate([np.asarray(g) for g in got]),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_slot_server_tokens_are_the_references_best():
    """Prefill then ragged decode through ``SlotServer`` (admission writes
    SSD, conv and ring rows into a slot; the chunk carries them), float32:
    every served token is the reference's best up to rounding, at the
    release's vocabulary (where the head does not echo the input)."""
    from repro.distributed import SlotConfig, SlotServer
    from repro.launch.mesh import make_host_mesh

    seed = 2 ** 31 + 23
    V = FULL_VOCAB["vocab_size"]
    params = R.init_weights(hybrid.declare(FULL_VOCAB),
                            jax.random.PRNGKey(seed))
    server = SlotServer(_arch(dtype="float32", vocab=V), make_host_mesh(),
                        SlotConfig(n_slots=3, ctx_len=32, steps_per_launch=4))
    prompts = np.random.default_rng(0).integers(0, V, (5, 16))
    res = server.serve(params, prompts.astype(np.int32), 12,
                       arrivals=np.array([0, 0, 1, 5, 9]))
    gaps = follow.serve_gaps(FULL_VOCAB, seed, prompts, res.tokens)
    # float32 program against the float32 reference: a served token may
    # trail the best only where the two are within rounding of a tie
    assert float(np.max(gaps)) < 1e-4, gaps


def test_async_trainer_runs_through_the_backend():
    from repro.api import ExperimentSpec, TrainJob, TrainerBackend

    job = TrainJob(arch="granite-4.0-h-micro", reduced=True, global_batch=8,
                   seq_len=32)
    spec = ExperimentSpec(scheduler="shuffled", timing="poisson:slow=6",
                          objective=job, T=4, n_workers=4, seed=0,
                          stepsize=3e-3)
    res = TrainerBackend().run(spec)
    assert len(res.losses) and np.isfinite(res.losses).all()
    assert np.isfinite(res.grad_norms).all()


# ---------------------------------------------------------- the whole run
def _run(capsys, monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    rc = run.main(["--workload", CELL, "--seed", SEED, "--seconds", "1",
                   "--trace", "0"], require_chip=False,
                  overrides={"config": HYBRID, "mix": _serve_mix(),
                             "limits": LIMITS})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


def test_serve_run_sees_an_altered_token(capsys, monkeypatch):
    from repro.distributed import slot_serve

    line = _run(capsys, monkeypatch)
    assert line["correct"] is True, line["checks"]
    orig = slot_serve.M.decode_step

    def altered(cfg, params, cache, tokens, pos, ctx_len):
        logits, cache = orig(cfg, params, cache, tokens, pos, ctx_len)
        return logits.at[:, 7].set(jnp.max(logits, -1) + 1.0), cache
    monkeypatch.setattr(slot_serve.M, "decode_step", altered)
    line = _run(capsys, monkeypatch)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("seed", [2147483659, 11, 2024])
def test_limit_parts_bfloat16_serving_from_the_control(seed):
    """At the release's vocabulary, bfloat16 serving through ``SlotServer``
    reads under the limit and the float8 control over it."""
    from repro.distributed import SlotConfig, SlotServer
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params

    arch = _arch(vocab=FULL_VOCAB["vocab_size"])
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, arch.vocab, (2, 16))
    params = jax.jit(lambda k: init_params(arch, k))(jax.random.PRNGKey(seed))
    server = SlotServer(arch, make_host_mesh(),
                        SlotConfig(n_slots=2, ctx_len=32, steps_per_launch=4))
    served = server.serve(params, prompts.astype(np.int32), 16).tokens
    sound = follow.serve_gaps(FULL_VOCAB, seed, prompts, served)
    low = follow.serve_gaps(FULL_VOCAB, seed, prompts, served,
                            precision="fp8")
    assert float(np.max(sound)) <= LIMITS["logit_gap"] < float(np.max(low))


# ----------------------------------------------------------------- counts
def test_parameters_and_state_by_hand():
    c = _cfg()
    # Mamba layer: norm 2048, in_z/in_x/out_proj 3·2048·4096, in_B/in_C
    # 2·2048·128, in_dt 2048·64, conv 4·4352 + 4352, A_log/D/dt_bias 3·64,
    # gate_norm 4096
    mamba = (2_048 + 25_165_824 + 524_288 + 131_072 + 17_408 + 4_352 + 192
             + 4_096)
    # attention: norm, q/o 2·2048·2048, k/v 2·2048·512; MLP: norm, 3·2048·8192
    attn = 2_048 + 8_388_608 + 2_097_152
    mlp = 2_048 + 50_331_648
    embed = 100_352 * 2_048
    assert counts.n_params(c) == 36 * mamba + 4 * attn + 40 * mlp + embed \
        + 2_048 == 3_191_396_096 == c["n_params"]
    # per slot: 36 × 64 × 64 × 128 f32 of SSD state, 36 × 3 × 4352 bf16 of
    # conv inputs; K/V: 4 layers × 2 × 8 heads × 64 × 2 bytes a position
    assert counts.state_bytes_per_slot(c) == 75_497_472 + 940_032 \
        == 76_437_504
    assert counts.kv_bytes_per_position(c) == 8_192
    n32 = 3 * 64 * 36
    assert counts.decode_weight_bytes(c, 64) == \
        (3_191_396_096 - n32) * 2 + n32 * 4 + 2 * 64 * 76_437_504
    assert counts.decode_flops(c, 0) == pytest.approx(
        2 * counts.matmul_params(c) + 4 * 1 * 32 * 64 * 4
        + 5 * 64 * 64 * 128 * 36)


def test_counts_match_the_program_at_tiny_size():
    from repro.distributed import SlotConfig, SlotServer
    from repro.launch.mesh import make_host_mesh
    from repro.models import n_params

    assert counts.n_params(HYBRID) == n_params(_arch())
    pool = SlotServer(_arch(), make_host_mesh(),
                      SlotConfig(n_slots=3, ctx_len=32)).pool_bytes()
    assert pool == {"ssm_state": 3 * counts.state_bytes_per_slot(HYBRID),
                    "kv": 3 * 32 * counts.kv_bytes_per_position(HYBRID)}


def test_pool_bytes_counter_is_recorded():
    from repro.distributed import SlotConfig, SlotServer
    from repro.launch.mesh import make_host_mesh
    from repro.obs import Recorder

    rec = Recorder()
    server = SlotServer(_arch(), make_host_mesh(),
                        SlotConfig(n_slots=2, ctx_len=32), recorder=rec)
    got = rec.summary()["counters"]
    assert got["slot_pool_bytes.kv"] == server.pool_bytes()["kv"] > 0
    assert got["slot_pool_bytes.ssm_state"] == \
        server.pool_bytes()["ssm_state"] > 0


# ------------------------------------------------- registry and the file
def test_registry_matches_the_configuration_file():
    """What ``check_widths`` cannot see for this family: the pattern,
    the heads, the MLP width, NoPE and the four multipliers."""
    from repro.configs import get_arch

    c, a = _cfg(), get_arch("granite-4.0-h-micro")
    letter = {"mamba": "M", "attention": "A"}
    pattern = "".join(letter[k] for k in c["layer_types"])
    assert a.layer_pattern * (a.n_layers // len(a.layer_pattern)) == pattern
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.d_head) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"],
        c["hidden_size"] // c["num_attention_heads"])
    assert a.d_ff == c["intermediate_size"] == c["shared_intermediate_size"]
    assert a.vocab == c["vocab_size"]
    assert a.rope is (c["position_embedding_type"] != "nope")
    assert a.qkv_bias is c["attention_bias"]
    assert a.n_experts == c["num_local_experts"] == 0
    assert (a.embedding_multiplier, a.residual_multiplier,
            a.attention_multiplier, a.logits_scaling) == (
        c["embedding_multiplier"], c["residual_multiplier"],
        c["attention_multiplier"], c["logits_scaling"])
    assert (a.ssm_state, a.ssm_head_dim, a.ssm_expand, a.ssm_conv,
            a.ssm_chunk, a.ssm_heads) == (
        c["mamba_d_state"], c["mamba_d_head"], c["mamba_expand"],
        c["mamba_d_conv"], c["mamba_chunk_size"], c["mamba_n_heads"])
    assert c["mamba_n_groups"] == 1 and c["mamba_conv_bias"] \
        and not c["mamba_proj_bias"] and c["hidden_act"] == "silu"
    assert (a.norm_eps, a.tie_embeddings) == (c["rms_norm_eps"],
                                              c["tie_word_embeddings"])
    # the keys check_widths reads restate the release's
    s = c["ssm_cfg"]
    assert (c["d_model"], c["n_layer"], c["tie_embeddings"]) == (
        c["hidden_size"], c["num_hidden_layers"], c["tie_word_embeddings"])
    assert (s["d_state"], s["headdim"], s["expand"], s["d_conv"],
            s["chunk_size"], s["ngroups"]) == (
        c["mamba_d_state"], c["mamba_d_head"], c["mamba_expand"],
        c["mamba_d_conv"], c["mamba_chunk_size"], c["mamba_n_groups"])


# ---------------------------------------------------------------- reader
def test_ssm_state_share_reads_the_state_ops():
    read = harness.metric_reader("ssm_state_share.serve")
    ops = {
        "%dynamic-update-slice.3 = f32[36,64,64,64,128]{4,3,2,1,0} "
        "dynamic-update-slice(f32[36,64,64,64,128]{4,3,2,1,0} %p, "
        "f32[1,64,64,64,128]{4,3,2,1,0} %u, s32[] %i)": 0.3,
        "%fusion.9 = (bf16[64,64,64]{2,1,0}, f32[64,64,64,128]{3,2,1,0}) "
        "fusion(f32[36,64,64,64,128]{4,3,2,1,0} %p)": 0.1,
        "%fusion.2 = bf16[64,8192]{1,0} fusion(bf16[64,2048]{1,0} %x, "
        "bf16[40,2048,8192]{2,1,0} %w)": 0.5,
    }
    tr = {"ops": ops, "window_s": 2.0, "n_devices": 1}
    assert read({}, tr) == pytest.approx(20.0)
    assert read({}, dict(tr, ops={k: v for k, v in ops.items()
                                  if "bf16[64,8192]" in k})) is None
    assert read({}, None) is None
