"""The :class:`Recorder` handle threaded through the runtime.

One ``Recorder`` per run is what the instrumented components accept
(``PlanExecutor``, ``SlotServer``, ``AsyncSnapshotter``, the backends,
``launch/train``): it owns a :class:`~repro.obs.tracer.Tracer`,
delegates the span/instant/metric primitives to it, and adds the
end-of-run :meth:`summary` dict that rides ``RunResult.extra["obs"]``
through serialization (plain scalars only — it must survive
``RunResult.to_json`` round-trips).

Instrumented call sites open their spans through :func:`span`, which
always writes a ``jax.profiler.TraceAnnotation`` (so the span lands in
any profiler trace, on the device's clock) and, with a recorder
attached, records the same span into its :class:`Tracer`.  Instants,
counters and gauges still guard with ``if recorder is not None`` — an
un-observed run formats nothing for them.
"""
from __future__ import annotations

from contextlib import contextmanager

from jax.profiler import TraceAnnotation

from .schema import METRICS_SCHEMA_VERSION
from .tracer import Tracer


def span(rec, name: str, lane: str, **args):
    """Context manager for one span of the program.

    Always opens ``TraceAnnotation(f"{lane}.{name}")``: with the profiler
    off that costs well under a microsecond, and under ``jax.profiler``
    the span sits in the host plane next to the device's ops.  With a
    :class:`Recorder` the span is also recorded in its tracer as
    ``name`` on ``lane`` with ``args`` (which then ride the annotation as
    its stats too); without one ``args`` are never formatted."""
    if rec is None:
        return TraceAnnotation(f"{lane}.{name}")
    return _recorded(rec, name, lane, args)


@contextmanager
def _recorded(rec, name, lane, args):
    with TraceAnnotation(f"{lane}.{name}", **args), \
            rec.span(name, lane, **args):
        yield


class Recorder:
    """Per-run observability handle: a Tracer plus summary assembly."""

    def __init__(self, tracer: Tracer = None):
        self.tracer = tracer if tracer is not None else Tracer()

    # -------------------------------------------------- tracer delegation
    def span(self, name, lane="main", **args):
        return self.tracer.span(name, lane, **args)

    def span_at(self, name, lane, start_ns, end_ns, **args):
        self.tracer.span_at(name, lane, start_ns, end_ns, **args)

    def instant(self, name, lane="main", **args):
        self.tracer.instant(name, lane, **args)

    def count(self, name, inc=1):
        self.tracer.count(name, inc)

    def gauge(self, name, value, lane="main"):
        self.tracer.gauge(name, value, lane)

    def hist(self, name, value):
        self.tracer.hist(name, value)

    def now_ns(self):
        return self.tracer.now_ns()

    def export_chrome(self, path: str) -> str:
        return self.tracer.export_chrome(path)

    def export_metrics(self, path: str) -> str:
        return self.tracer.export_metrics(path)

    # ----------------------------------------------------------- summary
    def summary(self, **extra) -> dict:
        """The machine-readable run summary (``RunResult.extra["obs"]``).

        ``phases`` is the span time-in-phase table, ``counters`` the
        final cumulative counts, ``hists`` the histogram summaries —
        everything :func:`repro.obs.render_summary` needs to print the
        human table, and the measurement substrate the ROADMAP's
        self-tuning item consumes.  ``extra`` keys (e.g. ``rounds``,
        ``tau_max``) merge in at the top level.
        """
        out = {
            "schema_version": METRICS_SCHEMA_VERSION,
            "wall_s": round(self.tracer.wall_s, 6),
            "phases": self.tracer.phase_table(),
            "counters": self.tracer.counters(),
            "hists": self.tracer.hist_summaries(),
        }
        out.update(extra)
        return out
