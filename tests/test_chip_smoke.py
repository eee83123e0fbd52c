"""chip_smoke.py's phases on the CPU at reduced width, and its refusal to
run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phases_agree_reduced(smoke):
    shape = smoke.size_train("pallas_pooled", reduced=True)
    assert shape == smoke.REDUCED_SHAPES[0]
    ref = smoke.train_phase("reference", shape, reduced=True)
    pooled = smoke.train_phase("pallas_pooled", shape, reduced=True)
    assert ref["impl"] == "reference"
    assert pooled["impl"] == "pallas_pooled_interpret"   # CPU: interpreter
    assert ref["losses"].shape == (smoke.ROUNDS,)
    smoke.check_losses_agree("train_pooled", ref["losses"], pooled["losses"])


def test_check_losses_agree_rejects_a_gap(smoke):
    with pytest.raises(AssertionError, match="losses differ"):
        smoke.check_losses_agree("x", np.array([10.0, 9.0]),
                                 np.array([10.0, 9.5]))


def test_serve_phase_reduced(smoke):
    out = smoke.serve_phase(reduced=True)
    assert out["tokens"].shape == (smoke.N_REQUESTS, smoke.GEN)
    assert 0 <= out["matches"] <= smoke.N_REQUESTS


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 phase on four virtual CPU devices at reduced width:
    (data=2, model=2) pools split over the data axis, losses match one
    device, collectives in the step."""
    code = ("import sys; sys.path.insert(0, 'src'); "
            "import importlib.util as u; "
            "s = u.spec_from_file_location('chip_smoke', 'chip_smoke.py'); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "m.four_chips(reduced=True)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "pool_share_per_device={0: 0.5, 1: 0.5, 2: 0.5, 3: 0.5}" \
        in r.stdout, r.stdout
