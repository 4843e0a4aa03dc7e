"""repro.obs — unified tracing + metrics for the LU pipeline (DESIGN.md §12).

Quickstart::

    import repro

    with repro.obs.tracing("trace.json"):       # Perfetto-loadable on exit
        plan = repro.analyze(a)
        factor = plan.factorize(values)
    print(plan.stats)                           # text summary tree
    print(repro.obs.metrics.registry().snapshot()["gauges"])

Disabled (the default) every instrumentation site is a module-level boolean
check — tier-1 timings and bitwise gates are unaffected.  Under a running
``jax.profiler`` session the spans annotate the profiler's trace as
``repro.<name>`` instead, with no option to set.
"""
from repro.obs import metrics, trace
from repro.obs.metrics import (
    MetricsRegistry, ProgressMeter, fraction_of_peak, registry,
    roofline_report, stderr_progress,
)
from repro.obs.trace import (
    SpanSummary, Tracer, device_track, disable, enable, ensure, fetch, put,
    span, traced, tracer, tracing,
)

__all__ = [
    "metrics", "trace",
    "MetricsRegistry", "ProgressMeter", "fraction_of_peak", "registry",
    "roofline_report", "stderr_progress",
    "SpanSummary", "Tracer", "device_track", "disable", "enable", "ensure",
    "fetch", "put", "span", "traced", "tracer", "tracing",
]
