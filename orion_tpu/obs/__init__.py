"""Observability: span tracing, flight recording, metrics registry, SLO
burn-rate monitoring.

The serving engine, the trainer, and the multi-replica router all thread
through this package (ISSUEs 9 + 14): ``Tracer`` is the host-side
span/event ring (Chrome trace-event export with overflow accounting;
``PhaseClock`` is the one span primitive of the engine's step phases:
host-time buckets, a ``jax.profiler`` annotation always, the ring when on;
``merge_chrome`` merges the router's ring plus N replica rings into one
Perfetto timeline on a shared clock), ``FlightRecorder`` the bounded
postmortem ring that auto-dumps on degradation triggers,
``MetricsRegistry`` the named-snapshot surface unifying the
per-subsystem Stats dataclasses (metrics.py) with pool occupancy and
live-HBM gauges, exportable as Prometheus textfiles and JSONL time
series, and ``SLOMonitor`` (obs/slo.py) the per-priority-class TTFT/ITL
objective judge emitting typed ``slo_breach`` events off windowed burn
rates.
"""

from orion_tpu.obs.flight import FlightRecorder, init_obs
from orion_tpu.obs.registry import (
    MetricsRegistry,
    bench_metrics_block,
    live_hbm_metrics,
)
from orion_tpu.obs.slo import SLOMonitor, SLOObjective, build_objectives
from orion_tpu.obs.trace import (
    NULL_TRACER,
    NullTracer,
    PhaseClock,
    Tracer,
    export_chrome_safe,
    merge_chrome,
    merge_chrome_safe,
    namespaced_path,
)

__all__ = [
    "FlightRecorder",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "PhaseClock",
    "SLOMonitor",
    "SLOObjective",
    "Tracer",
    "bench_metrics_block",
    "build_objectives",
    "export_chrome_safe",
    "init_obs",
    "live_hbm_metrics",
    "merge_chrome",
    "merge_chrome_safe",
    "namespaced_path",
]
