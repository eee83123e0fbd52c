"""Whole training step's share of the chip's bf16 peak: the FLOPs the
trained tokens need (forward and backward, no recomputation; see
``bench/counts``) over the traced window.  Tokens of worker groups that
take no part in a round are computed but not trained, so they do not
count.  Moves ``train_tokens_per_s``."""


def read(rec, tr):
    if not rec.get("peak") or tr is None or tr["window_s"] <= 0:
        return None
    flops = rec["trained_tokens"] * rec["flops_per_trained_token"]
    return 100.0 * flops / tr["window_s"] / rec["peak"]["bf16_flops_per_s"]
