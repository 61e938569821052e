"""The tracer's two ties to JAX (DESIGN.md §14).

``attach(tracer)`` hands the tracer ``jax.profiler.TraceAnnotation`` as
its annotation factory, so each of its spans also sits by name on the
host plane of any ``jax.profiler`` trace, on the device trace's clock;
and it adds the tracer to the sinks of one process-wide
``jax.monitoring`` listener that records every backend compile as a
``jax.compile`` span.  The compile span runs on the thread that
compiled and ends when JAX reports the compile's duration; a load from
the persistent compilation cache happens inside that duration, so it
fires too.  ``TrainEngine`` attaches the process tracer and each
``ForecastEngine`` its own; a tracer leaves the sinks when it is
garbage-collected.
"""
from __future__ import annotations

import threading
import weakref

import jax

from repro.telemetry.spans import Tracer

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# jax.monitoring listeners are process-wide and registered once, so the
# tracers they feed are too
_SINKS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_LOCK = threading.Lock()
_REGISTERED = False


def _on_duration(event: str, duration_s: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    end = Tracer.now_ns()
    start = end - int(duration_s * 1e9)
    with _LOCK:
        sinks = list(_SINKS)
    for tr in sinks:
        tr.complete("jax.compile", start, end, fun=kw.get("fun_name"))


def attach(tracer: Tracer) -> Tracer:
    """Annotate ``tracer``'s spans in profiler traces and record the
    process's backend compiles into it; returns the tracer."""
    global _REGISTERED
    tracer.annotate = jax.profiler.TraceAnnotation
    with _LOCK:
        if not _REGISTERED:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _REGISTERED = True
        _SINKS.add(tracer)
    return tracer
