"""``repro.obs`` — unified tracing + metrics across the runtime.

Observability substrate: a :class:`Tracer` collecting host-timestamped
spans / instants / metrics at EXISTING host boundaries (never a new
device sync), a per-run :class:`Recorder` handle threaded through
``PlanExecutor``, ``SlotServer``, ``AsyncSnapshotter`` and the fault
guards, the one span helper :func:`span` (every span is also a
``jax.profiler.TraceAnnotation``, so it appears in any profiler trace
next to the device's ops, recorder or not), a :class:`CompileWatch`
retrace sentinel generalising ``SlotServer.compile_counts`` with
:func:`compile_log` timing every compile, Chrome-trace-event export
(Perfetto) + a schema-versioned JSONL metrics log, and
:func:`render_summary` for the human time-in-phase table.

    from repro.obs import Recorder, render_summary

    rec = Recorder()
    res = TrainerBackend(recorder=rec).run(spec)
    rec.export_chrome("trace.json")      # -> ui.perfetto.dev
    rec.export_metrics("metrics.jsonl")  # -> schema-validated log
    print(render_summary(res.extra["obs"], trace=res.trace))
"""
from .compile_watch import CompileWatch, RetraceError, compile_log
from .recorder import Recorder, span
from .schema import (METRICS_SCHEMA_VERSION, SchemaError, validate_line,
                     validate_lines, validate_metrics_log)
from .summary import render_summary
from .tracer import Tracer

__all__ = [
    "CompileWatch", "RetraceError", "Recorder", "Tracer", "compile_log",
    "span",
    "METRICS_SCHEMA_VERSION", "SchemaError", "validate_line",
    "validate_lines", "validate_metrics_log", "render_summary",
]
