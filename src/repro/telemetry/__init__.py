"""Unified telemetry subsystem (DESIGN.md §14): structured spans,
counters/gauges/histograms, analytic step-cost counts, Chrome-trace +
JSONL export, and the spans' place in ``jax.profiler`` traces.

Facade:

    from repro import telemetry
    tr = telemetry.get_tracer()          # process tracer (always valid)
    with tr.span("data_wait", step=i):   # monotonic-clock span
        ...
    tr.counter("pipeline.batches")
    tr.gauge("pipeline.queue_depth", 2)
    tr.observe("serve.latency_s", 0.12)

    tr.step_record(step=i, dur_s=dt, data_wait_s=w)
    telemetry.attach_profiler(tr)        # spans in jax.profiler traces,
                                         # backend compiles as spans

    tr.export_chrome("out.trace.json")   # Perfetto / chrome://tracing
    tr.export_jsonl("out.trace.jsonl")   # launch/trace_report.py input

The tracer side (``spans.py``) never imports jax; ``profiler.py`` ties
it to JAX; the accounting side (``accounting.py``) reuses the
exact-dims FLOPs model from ``launch/analysis.py`` and the ring
schedule from ``core/jigsaw.py``.
"""
from repro.telemetry.accounting import (StepCostModel, build_cost_model,
                                        fig7_point, hlo_collective_bytes)
from repro.telemetry.profiler import attach as attach_profiler
from repro.telemetry.spans import (Span, Tracer, get_tracer,
                                   jsonl_path_for, set_tracer)

__all__ = [
    "Span", "StepCostModel", "Tracer", "attach_profiler",
    "build_cost_model", "fig7_point", "get_tracer", "hlo_collective_bytes",
    "jsonl_path_for", "set_tracer",
]
