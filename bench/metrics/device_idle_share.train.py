"""Share of the traced training window in which no XLA op ran on the
device (1 − union of op intervals / window).  Moves
``train_tokens_per_s``."""


def read(rec, tr):
    if tr is None or tr["window_s"] <= 0 or not tr["n_devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
