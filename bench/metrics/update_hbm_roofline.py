"""The delayed Adam update's share of its HBM roofline: the bytes one
update needs (``bench/counts``: read p, g, m, v, gbuf, write p, m, v, gbuf,
at the state's dtypes, no padding) over the HBM peak, divided by the
device time of the update kernels per round.  Moves ``train_tokens_per_s``.

The kernels are the pooled delayed-Adam Pallas kernels, one per dtype
pool.  They carry no name of their own in the trace (the op is a
``closed_call``), so they are found by their signature: a Mosaic custom
call whose first operand is the five SMEM scalars (lr, the two bias
corrections, the clip factor, weight decay)."""
from bench.trace import time_matching

KERNEL = r'custom-call\(f32\[5\][^,]*,.*custom_call_target="tpu_custom_call"'


def read(rec, tr):
    if not rec.get("peak") or tr is None:
        return None
    t = time_matching(tr["ops"], KERNEL)
    if t <= 0:
        return None
    per_round = t / rec["rounds"]
    return 100.0 * rec["update_bytes"] / rec["peak"]["hbm_bytes_per_s"] \
        / per_round
