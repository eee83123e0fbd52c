"""Share of the traced serving window the device spends in ops that read
or write the float32 recurrent state: every op whose HLO text holds a
float32 array whose last three dims are a Mamba-2 layer's SSD state of
64 heads × 64 × 128 (one slot's, one layer's or the whole stack's; in the
decode chunk, a prefill or an admission), by device time over the window.
The ops carry no name of their own in the trace, so they are found by that
signature, as ``update_hbm_roofline`` finds its kernels.  Moves
``serve_tokens_per_s``."""
from bench.trace import time_matching

STATE = r"f32\[(\d+,)*64,64,128\]"


def read(rec, tr):
    if tr is None or tr["window_s"] <= 0 or not tr["n_devices"]:
        return None
    t = time_matching(tr["ops"], STATE)
    if t <= 0:
        return None
    return 100.0 * t / tr["window_s"]
