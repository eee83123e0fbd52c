"""Retrace sentinel: watch cached jits for steady-state recompilation.

The repo's compiled drivers (``PlanExecutor``, ``SlotServer``) live and
die by ONE rule: the jitted programs are cached on the instance and must
never re-trace once warm — a silent retrace turns a 5.6× dispatch win
into a recompile-per-run regression (found twice already: a tiler that
built a fresh closure per call, and a decode loop that built a fresh
``jax.jit`` per call).  :class:`CompileWatch` generalises the
``SlotServer.compile_counts`` gate those fixes hand-rolled:

* :meth:`wrap` wraps any cached jit; after each call the traced-signature
  count (``fn._cache_size()``) is compared to the last seen value and
  every growth is recorded as a ``compile`` trace instant (plus a
  ``compiles`` counter) on the attached recorder — compile events land in
  the trace next to the launch that triggered them.
* :meth:`counts` is the machine-readable registry snapshot (the old
  ``compile_counts()`` shape).
* :meth:`mark_steady` / :meth:`check_steady` assert the zero-steady-state-
  retrace contract: snapshot the counts once warm, then any later growth
  raises :class:`RetraceError` naming the offending program.
* :func:`compile_log` says how long each compile took.  One process-wide
  listener on JAX's compile events (trace to jaxpr, lowering to MLIR,
  backend compile or persistent-cache load) credits each event to the
  watched program whose wrapped call is in progress on that thread, or
  to ``None`` where no wrapped call is: the compiles no watch sees.  A
  wrapped call's events are kept if it grew the signature count.  Where
  that count cannot be read, and where no watch sees the call, a trace
  is kept only with the lowering that follows it: alone, it was a lookup
  in JAX's traced-jaxpr cache, which a jit off the C++ dispatch path (one
  holding an ordered ``io_callback``) makes on every call.  With a
  recorder attached, each credited event is also a ``compile`` span
  (lane ``compile``) and adds to the ``compile_s`` counter.

The per-call overhead is a push and pop of a thread-local stack and one
``_cache_size()`` read (a host-side dict ``len``) at boundaries that
already dispatch an XLA program — nothing on the device path.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Callable, NamedTuple, Optional

import jax.monitoring

#: JAX's compile events (``jax._src.dispatch``) -> the phase they time;
#: ``backend`` includes a load from the persistent compilation cache
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileEntry(NamedTuple):
    """One timed compile phase.  ``start_s``/``end_s`` are ``time.time()``
    seconds; ``program`` is the watched name, ``None`` when unwatched."""
    program: Optional[str]
    fun_name: str
    phase: str
    start_s: float
    end_s: float
    cache_hit: bool


# Process-wide by nature: JAX's listeners are.  ``_calls.stack`` holds
# the entries timed so far of each wrapped call in progress on this
# thread; ``_calls.traced`` the unwatched traces awaiting a lowering;
# ``_calls.hit`` marks a cache hit inside the backend phase in progress.
_calls = threading.local()
_log: list = []
_log_lock = threading.Lock()


def _stack() -> list:
    st = getattr(_calls, "stack", None)
    if st is None:
        st = _calls.stack = []
    return st


def _compiled(entries) -> bool:
    """True where the phases hold more than traces (see module doc)."""
    return any(e[1] != "trace" for e in entries)


def _on_time_span(event, start_time, end_time, **kw):
    phase = _PHASES.get(event)
    if phase is None:
        return
    hit = False
    if phase == "backend":
        hit, _calls.hit = getattr(_calls, "hit", False), False
    st = getattr(_calls, "stack", None)
    start, end = float(start_time), float(end_time)
    entry = (str(kw.get("fun_name", "")), phase, start, end, hit)
    if st:
        st[-1].append(entry)           # credited when the call returns
    elif phase == "trace":
        # a nested jit's trace ends inside its caller's; an earlier trace
        # the new one does not hold was a lookup, and is dropped
        _calls.traced = [e for e in getattr(_calls, "traced", ())
                         if start <= e[2] and e[3] <= end] + [entry]
    else:
        _commit(None, getattr(_calls, "traced", []) + [entry])
        _calls.traced = []


def _commit(name, entries) -> list:
    out = [CompileEntry(name, *e) for e in entries]
    with _log_lock:
        _log.extend(out)
    return out


def _on_event(event, **kw):
    if event == _CACHE_HIT:
        _calls.hit = True


jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_listener(_on_event)


def compile_log() -> list:
    """Every compile phase this process has timed since ``repro.obs`` was
    imported, as :class:`CompileEntry` tuples ``(program, fun_name, phase,
    start_s, end_s, cache_hit)`` in the order they were credited (a
    wrapped call's when it returns)."""
    with _log_lock:
        return list(_log)


def _covered_s(intervals) -> float:
    """Length of the union of ``[(start, end), ...]`` (trace phases of
    nested jits lie inside their caller's)."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


class RetraceError(RuntimeError):
    """A watched jit re-traced after :meth:`CompileWatch.mark_steady`."""


def _cache_size(fn) -> int:
    sizer = getattr(fn, "_cache_size", None)
    return int(sizer()) if sizer is not None else -1


class CompileWatch:
    """Registry of cached jits + their traced-signature counts."""

    def __init__(self, recorder=None, lane: str = "compile"):
        self.recorder = recorder
        self.lane = lane
        self._fns: dict = {}       # name -> the underlying jitted fn
        self._seen: dict = {}      # name -> last observed signature count
        self._steady: Optional[dict] = None
        self._compiled: list = []  # (start_s, end_s) credited to this watch

    def register(self, name: str, fn) -> None:
        """Track ``fn`` without wrapping (counts/steady checks only)."""
        self._fns[name] = fn
        self._seen.setdefault(name, _cache_size(fn))

    def wrap(self, name: str, fn) -> Callable:
        """Track ``fn`` AND return a call-through wrapper that records a
        ``compile`` instant whenever a call grew the traced-signature
        count (i.e. this call paid a trace+compile), and credits the
        compile events timed during the call to ``name``
        (:func:`compile_log`)."""
        self.register(name, fn)

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            st = _stack()
            timed = []
            st.append(timed)
            try:
                out = fn(*args, **kw)
            finally:
                st.pop()
            grew = self._note(name)
            if timed and (grew or grew is None and _compiled(timed)):
                self._credit(name, timed)
            return out

        wrapped.__wrapped_jit__ = fn
        return wrapped

    def _note(self, name: str) -> Optional[bool]:
        """Record growth of ``name``'s signature count; whether it grew,
        or ``None`` where the count cannot be read."""
        now = _cache_size(self._fns[name])
        last = self._seen.get(name, 0)
        if now < 0:
            return None
        if now > last:
            self._seen[name] = now
            rec = self.recorder
            if rec is not None:
                rec.instant("compile", lane=self.lane, fn=name,
                            signatures=now)
                rec.count("compiles", now - max(last, 0))
            return True
        return False

    def _credit(self, name: str, entries: list) -> None:
        """Log the compile phases of a wrapped call that compiled."""
        logged = _commit(name, entries)
        rec = self.recorder
        if rec is None:
            return
        now_ns, now_s = rec.now_ns(), time.time()
        for e in logged:
            start_ns = now_ns - int((now_s - e.start_s) * 1e9)
            rec.span_at("compile", self.lane, start_ns,
                        start_ns + int((e.end_s - e.start_s) * 1e9),
                        fn=name, phase=e.phase, cache_hit=e.cache_hit)
        before = _covered_s(self._compiled)
        self._compiled.extend((e.start_s, e.end_s) for e in logged)
        rec.count("compile_s", _covered_s(self._compiled) - before)

    def observe(self) -> dict:
        """Re-read every registered fn (for jits called outside their
        wrappers) and record instants for any growth; returns counts."""
        for name in self._fns:
            self._note(name)
        return self.counts()

    def counts(self) -> dict:
        """``{name: traced-signature count}`` for every registered jit."""
        return {name: _cache_size(fn) for name, fn in self._fns.items()}

    # ------------------------------------------------------- steady contract
    def mark_steady(self) -> dict:
        """Snapshot the current counts as the allowed steady state (call
        once the driver is warm — after the first full run, which may
        legitimately trace e.g. a ragged-tail chunk length)."""
        self._steady = self.counts()
        return dict(self._steady)

    def check_steady(self) -> None:
        """Raise :class:`RetraceError` if any watched jit traced a new
        signature since :meth:`mark_steady`."""
        if self._steady is None:
            raise RetraceError(
                "check_steady() before mark_steady(): nothing to compare "
                "against")
        grown = {name: (self._steady.get(name, 0), now)
                 for name, now in self.counts().items()
                 if now > self._steady.get(name, 0)}
        if grown:
            detail = ", ".join(f"{n}: {a} -> {b}"
                               for n, (a, b) in sorted(grown.items()))
            raise RetraceError(
                f"steady-state retrace detected ({detail}) — a cached "
                "program specialised on something that varies per call")
