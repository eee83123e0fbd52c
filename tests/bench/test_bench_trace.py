"""The trace reduction: merged busy intervals, the idle share, device time
by op and program name, and the breakdown, on a small trace recorded on a
TPU v5e (``data/tiny_v5e.xplane.pb``: three calls of a jitted matmul
followed by a Pallas kernel named ``double_kernel``, inside a
``bench_window`` annotation).  Recorded with::

    with jax.profiler.trace(dir), TraceAnnotation("bench_window"):
        for _ in range(3):
            step(a).block_until_ready()
"""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data" / "tiny_v5e.xplane.pb"


def test_union_merges_overlaps():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]


def test_idle_gaps_are_named_by_the_host_span():
    red = {"window_ns": (0, 100), "busy": [[[10, 20], [60, 90]]],
           "host": [(30, 55, "dispatch", "python"),
                    (0, 100, "outer", "python")]}
    gaps = T.idle_gaps(red)
    assert gaps[0] == ["dispatch", 40e-9]
    assert sorted(g[1] for g in gaps) == [10e-9, 10e-9, 40e-9]


@pytest.fixture(scope="module")
def red():
    return T.reduce(str(DATA))


def test_window_and_busy(red):
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"] < 1.0
    merged = red["busy"][0]
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    busy = sum(e - s for s, e in merged) / 1e9
    assert busy == pytest.approx(red["busy_s"])
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0 < idle < 1


def test_kernel_time_by_name(red):
    kern = T.time_matching(red["ops"], "double")
    assert 0 < kern < red["busy_s"]
    assert T.time_matching(red["modules"], r"^jit_step$") >= kern
    # the device clock lands within a few milliseconds of the host's, so
    # the first of the three calls may fall before the host's window
    assert 1 <= red["module_events"].get("jit_step") <= 3


def test_breakdown_shape(red):
    b = T.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
