"""The serving window's share of the chip's bf16 peak: the FLOPs of the
prefills admitted and the tokens decoded in the traced window
(``bench/counts``) over its length.  Moves ``serve_tokens_per_s``."""


def read(rec, tr):
    if not rec.get("peak") or tr is None or tr["window_s"] <= 0 \
            or "trace_flops" not in rec:
        return None
    return 100.0 * rec["trace_flops"] / tr["window_s"] \
        / rec["peak"]["bf16_flops_per_s"]
