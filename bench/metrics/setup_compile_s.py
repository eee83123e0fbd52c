"""Seconds the process spent compiling the programs ``CompileWatch``
watches: the union of the compile phases that ``repro.obs.compile_log()``
credits to a watched program (trace to jaxpr, lowering, and the backend
compile or its load from the persistent cache).  A run in which a
watched program compiles inside the window is refused, so this is set-up
time.  ``None`` where the program keeps no such log.  Moves
``setup_s``."""
from bench.trace import _union


def read(rec, tr):
    try:
        from repro.obs import compile_log
    except ImportError:
        return None
    spans = [(e.start_s, e.end_s) for e in compile_log()
             if e.program is not None]
    if not spans:
        return None
    return sum(e - s for s, e in _union(spans))
