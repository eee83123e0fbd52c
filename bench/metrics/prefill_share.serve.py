"""Share of the traced serving window the device spends in the batch-1
prefill and admission programs (``jit_pf``, ``jit_admit``), by their XLA
module time.  Moves ``itl_p95_ms``: every chunk boundary's prefills sit
inside one inter-token gap of every running request."""
from bench.trace import time_matching

PROGRAMS = r"^jit_(pf|admit)$"


def read(rec, tr):
    if tr is None or tr["window_s"] <= 0 or not tr["n_devices"]:
        return None
    return 100.0 * time_matching(tr["modules"], PROGRAMS) / tr["window_s"]
