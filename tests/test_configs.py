"""Exact assigned dimensions — guards against accidental config drift."""
import pytest

from repro.configs import ARCHS, get_arch, SHAPES

ASSIGNED = {
    # name: (family, L, d_model, H, kv, d_ff, vocab)
    "grok-1-314b": ("moe", 64, 6144, 48, 8, 32768, 131072),
    "deepseek-moe-16b": ("moe", 28, 2048, 16, 16, 1408, 102400),
    "minitron-8b": ("dense", 32, 4096, 32, 8, 16384, 256000),
    "qwen2-0.5b": ("dense", 24, 896, 14, 2, 4864, 151936),
    "stablelm-1.6b": ("dense", 24, 2048, 32, 32, 5632, 100352),
    "zamba2-7b": ("hybrid", 81, 3584, 32, 32, 14336, 32000),
    "mamba2-370m": ("ssm", 48, 1024, 0, 0, 0, 50280),
    "seamless-m4t-large-v2": ("audio", 24, 1024, 16, 16, 8192, 256206),
    "pixtral-12b": ("vlm", 40, 5120, 32, 8, 14336, 131072),
    "qwen3-8b": ("dense", 36, 4096, 32, 8, 12288, 151936),
    "granite-4.0-h-micro": ("hybrid", 40, 2048, 32, 8, 8192, 100352),
}


def test_all_ten_assigned_archs_present():
    assert sorted(ARCHS) == sorted(ASSIGNED)


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_exact_assigned_dims(name):
    fam, L, d, H, kv, ff, V = ASSIGNED[name]
    c = get_arch(name)
    assert (c.family, c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
            c.d_ff, c.vocab) == (fam, L, d, H, kv, ff, V)


def test_assigned_details():
    g = get_arch("grok-1-314b")
    assert g.n_experts == 8 and g.top_k == 2
    ds = get_arch("deepseek-moe-16b")
    assert ds.n_experts == 64 and ds.top_k == 6 and ds.n_shared_experts == 2
    assert get_arch("qwen2-0.5b").qkv_bias
    assert get_arch("qwen3-8b").qk_norm
    z = get_arch("zamba2-7b")
    assert z.ssm_state == 64 and z.attn_every == 6
    assert get_arch("mamba2-370m").ssm_state == 128
    assert get_arch("seamless-m4t-large-v2").enc_layers == 24
    assert get_arch("pixtral-12b").n_patches > 0
    g = get_arch("granite-4.0-h-micro")
    assert g.layer_pattern == "MMMMMAMMMM" and not g.rope
    assert g.ssm_state == 128 and g.ssm_chunk == 256 and g.tie_embeddings


def test_assigned_shapes():
    assert SHAPES["train_4k"].seq_len == 4096 and SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768 and SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].seq_len == 32768 and SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288 and SHAPES["long_500k"].global_batch == 1
    assert SHAPES["decode_32k"].kind == "decode" and SHAPES["long_500k"].kind == "decode"


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_reduced_variants_within_smoke_budget(name):
    c = get_arch(name)
    r = c.reduced()
    # a layer pattern keeps one whole period, every kind of layer in it
    assert r.n_layers <= max(2, len(c.layer_pattern))
    assert r.layer_pattern == c.layer_pattern
    assert r.d_model <= 512
    if r.n_experts:
        assert r.n_experts <= 4
    if r.n_heads:
        assert r.n_heads % r.n_kv_heads == 0
