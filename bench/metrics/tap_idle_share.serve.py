"""Share of the traced serving window in which device 0 runs no XLA op
while the host is inside a ``server.tap`` annotation (the slot server's
io_callback sink, a Python loop over every slot each decode step; its
host transfer does not count as device work), over the window.  Idle
time inside the spans is their union with the busy intervals less the
busy time.  Moves ``serve_tokens_per_s``: the next decode step waits for
the ordered tap."""
from bench.trace import _clip, _union

SPAN = "server.tap"


def _length(intervals):
    return sum(e - s for s, e in intervals)


def read(rec, tr):
    if tr is None or not tr["busy"]:
        return None
    lo, hi = tr["window_ns"]
    spans = [_clip(s, e, lo, hi) for s, e, name, _ in tr["host"]
             if name == SPAN]
    spans = [(s, e) for s, e in spans if e > s]
    if not spans:
        return None
    busy = tr["busy"][0]
    idle = _length(_union(spans + [tuple(iv) for iv in busy])) \
        - _length(busy)
    return 100.0 * idle / (hi - lo)
