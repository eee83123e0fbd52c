"""The decode chunk's share of its HBM roofline: the bytes one decode
step needs (every weight once, and each active request's live KV rows up
to its position; ``bench/counts``) over the HBM peak, divided by the
chunk program's (``jit_chunk``) device time per decode step in the traced
window.  Moves ``serve_tokens_per_s``."""
from bench.trace import time_matching

PROGRAM = r"^jit_chunk$"


def read(rec, tr):
    if not rec.get("peak") or tr is None or not rec.get("trace_steps"):
        return None
    t = time_matching(tr["modules"], PROGRAM)
    if t <= 0:
        return None
    per_step = t / rec["trace_steps"]
    return 100.0 * rec["decode_bytes_per_step"] \
        / rec["peak"]["hbm_bytes_per_s"] / per_step
