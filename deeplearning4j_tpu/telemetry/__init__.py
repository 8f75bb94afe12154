"""Runtime observability: metrics registry, step tracing, XLA compile
watching, resource watermarks.

The model/listener layer (`optimize/listeners.py`, `ui/stats.py`) answers
"is the MODEL learning"; this package answers "is the RUNTIME healthy" —
XLA compilation churn, host-vs-device time split, dispatch stalls, memory
watermarks. Dapper-style always-on tracing (Sigelman et al., 2010) applied
to the jitted training loop: a disabled session costs one global read per
step, an enabled one a few microseconds per span.

Four pieces:
  * `MetricsRegistry` (registry.py) — thread-safe counters / gauges /
    histograms / timers with Prometheus-text and JSONL exporters.
  * `Tracer` (tracing.py) — the span log: one process-wide ring
    (`tracer()`, `install_tracer()` for tests, `enabled`) that
    `telemetry.span(name, **attrs)` writes to, also as
    `jax.profiler.TraceAnnotation`s inside a profiler session; exports
    Chrome trace-event JSON, loadable in Perfetto / chrome://tracing.
  * `CompileWatcher` (compile_watch.py) — counts XLA compilations per
    jitted entry point and warns on recompilation storms from shape churn
    (the silent TPU killer).
  * `ResourceWatermarks` (resources.py) — host RSS + live device buffer
    bytes, current and peak.

`TelemetrySession` (runtime.py) bundles them; `telemetry.enable()` installs
the process-wide session the instrumented hot paths consult.
`TelemetryListener` (listener.py) wires per-iteration metrics into the
existing listener chain without touching StatsListener/UI.

Request-level observability (ISSUE 17):
  * `TraceContext` / `SloSurface` (trace_context.py) — per-request
    correlation ids threaded HTTP -> batcher -> decode scheduler/engine,
    plus declared per-tier latency SLOs with burn-rate gauges.
  * `FlightRecorder` (recorder.py) — always-on lock-free ring of
    structured events; `fault/guard.py` dumps it on skip/rollback/halt
    and the server exposes it at /debug/flightrecord.
"""
from .compile_watch import CompileWatcher, watch_compiles
from .listener import TelemetryListener
from .recorder import FlightRecorder, flight_recorder, install
from .registry import (Counter, Gauge, Histogram, MetricsRegistry, Timer)
from .resources import ResourceWatermarks
from .runtime import (TelemetrySession, active, disable, enable, enabled,
                      span)
from .trace_context import SloSurface, TraceContext
from .tracing import Tracer, install as install_tracer, tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "Timer", "MetricsRegistry",
    "Tracer", "tracer", "install_tracer", "span", "CompileWatcher", "watch_compiles", "ResourceWatermarks",
    "TelemetrySession", "TelemetryListener",
    "TraceContext", "SloSurface", "FlightRecorder", "flight_recorder",
    "install",
    "active", "enable", "disable", "enabled",
]
