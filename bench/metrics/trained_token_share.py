"""Share of the tokens the step computes whose gradient enters an applied
update, from the plan's participation masks of the traced window (an
exact count).  Moves ``train_tokens_per_s``."""


def read(rec, tr):
    if not rec.get("computed_tokens"):
        return None
    return 100.0 * rec["trained_tokens"] / rec["computed_tokens"]
