"""The yardstick's counts against hand-worked numbers, and the parameter
counts against the program's own model at a tiny size."""
import json

import pytest

from bench.counts import dense, ssm
from tiny import DENSE, ROOT, SSM


def _cfg(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_qwen2_parameters_by_hand():
    c = _cfg("qwen2-0.5b")
    # per layer: q 896·896, k/v 2·896·128, o 896·896, MLP 3·896·4864,
    # biases 896 + 2·128, two norms 2·896
    layer = 802_816 + 229_376 + 802_816 + 13_074_432 + 1_152 + 1_792
    embed = 151_936 * 896
    assert dense.n_params(c) == 24 * layer + embed + 896 == 494_032_768


def test_mamba2_parameters_by_hand():
    c = _cfg("mamba2-370m")
    # per layer: in_z/in_x 2·1024·2048, in_B/in_C 2·1024·128, in_dt 1024·32,
    # conv 4·2304 + 2304, A_log/D/dt_bias 3·32, norm 1024, gate_norm 2048,
    # out_proj 2048·1024
    layer = (4_194_304 + 262_144 + 32_768 + 9_216 + 2_304 + 96 + 1_024
             + 2_048 + 2_097_152)
    embed = head = 50_280 * 1024
    assert ssm.n_params(c) == 48 * layer + embed + head + 1024 \
        == 419_825_152


def test_flops_per_trained_token_by_hand():
    c = _cfg("qwen2-0.5b")
    matmul = 24 * 14_909_440 + 136_134_656
    attn = 4 * 512.5 * 14 * 64 * 24
    assert dense.train_flops_per_token(c, 1024) == pytest.approx(
        3 * (2 * matmul + attn))
    m = _cfg("mamba2-370m")
    mm = 48 * (4_194_304 + 262_144 + 32_768 + 9_216 + 2_097_152) \
        + 51_486_720
    per = 2 * 64.5 * 128 + 2 * 64.5 * 2048 + 4 * 2048 * 128
    assert ssm.train_flops_per_token(m, 1024) == pytest.approx(
        3 * (2 * mm + 48 * per))


def test_update_bytes_by_hand():
    assert dense.update_bytes(_cfg("qwen2-0.5b")) == 494_032_768 * 26
    n32 = 3 * 32 * 48
    assert ssm.update_bytes(_cfg("mamba2-370m")) == \
        (419_825_152 - n32) * 26 + n32 * 36


def test_decode_bytes_by_hand():
    c = _cfg("qwen2-0.5b")
    assert dense.kv_bytes_per_position(c) == 24 * 2 * 2 * 64 * 2
    assert dense.decode_weight_bytes(c, 128) == 494_032_768 * 2
    assert dense.decode_flops(c, 0) == pytest.approx(
        2 * dense.matmul_params(c) + 4 * 1 * 14 * 64 * 24)


@pytest.mark.parametrize("cfg,mod", [(DENSE, dense), (SSM, ssm)],
                         ids=["dense", "ssm"])
def test_counts_match_the_program_at_tiny_size(cfg, mod):
    from repro.configs import get_arch
    from repro.models import n_params

    arch = get_arch(cfg["registry"]).reduced()
    assert mod.n_params(cfg) == n_params(arch)
