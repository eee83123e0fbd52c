"""Tiny stand-ins of the benchmark's configurations and mixes, for driving
whole runs on the CPU.  Each configuration is the registry's smoke
variant (``registry_reduced``) of a configuration the benchmark runs."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

DENSE = {
    "name": "qwen2-0.5b", "registry": "qwen2-0.5b", "registry_reduced": True,
    "family": "dense", "hidden_size": 256, "intermediate_size": 512,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True,
}
SSM = {
    "name": "mamba2-370m", "registry": "mamba2-370m",
    "registry_reduced": True, "family": "ssm", "d_model": 256,
    "n_layer": 2, "vocab_size": 512, "tie_embeddings": False,
    "rms_norm_eps": 1e-5,
    "ssm_cfg": {"d_state": 32, "d_conv": 4, "expand": 2, "headdim": 32,
                "ngroups": 1, "chunk_size": 16},
}


def mix(name: str, **over) -> dict:
    with open(ROOT / "bench" / "mixes" / f"{name}.json") as f:
        m = json.load(f)
    m.update(over)
    return m


def train_mix(name="train.shuffled"):
    return mix(name, seq_len=64, global_batch=8, max_launches_per_s=1,
               rounds_per_launch=4)


def serve_mix():
    return mix("serve.saturated", n_slots=8, prompt_len=16, max_new=16,
               arrival_gap_steps=2, ramp_steps=16, warmup_requests=8,
               warmup_new=8, sample_requests=2, window_margin=3.0)


#: limits for the tiny stand-ins, set by the cells' own rule from readings
#: at this size on the CPU: the largest reading of sound runs (four seeds)
#: below, the float8 control and the planted faults above.
#: Sound: loss_gap ≤ 3.4e-5 / 1.8e-4 / 4.4e-5, gnorm_gap ≤ 2.0e-3 /
#: 1.3e-3 / 1.8e-3, change_gap ≤ 0.019 / 0.024 / 0.012 (qwen2 shuffled /
#: mamba2 shuffled / qwen2 waiting); logit_gap 0.
#: Control: loss_gap ≥ 2.0e-4 / 5.6e-4 / 9.4e-5, gnorm_gap ≥ 1.5e-2 /
#: 4.9e-3 / 1.3e-2; logit_gap ≥ 0.036.  Half the rows left out: loss_gap
#: ≥ 1.3e-2 / 1.2e-2 / 5.9e-3, gnorm_gap ≥ 0.16 / 0.35 / 0.39.  A state
#: left unchanged: change_gap 1.
LIMITS = {
    "train.qwen2-0.5b.shuffled": {"loss_gap": 1e-3, "gnorm_gap": 6e-3,
                                  "change_gap": 0.1},
    "train.mamba2-370m.shuffled": {"loss_gap": 5e-3, "gnorm_gap": 3e-3,
                                   "change_gap": 0.1},
    "train.qwen2-0.5b.waiting": {"loss_gap": 1.2e-3, "gnorm_gap": 5e-3,
                                 "change_gap": 0.1},
    "serve.qwen2-0.5b.saturated": {"logit_gap": 0.012},
}
