"""Unified cluster observability: live metrics + span tracing.

- ``registry`` — thread-safe counters / gauges / log-bucket histograms
  with labels; compact wire form for the heartbeat metrics payload.
- ``tracer`` — spans (wall-clock anchor + monotonic duration) exported as
  Chrome trace-event JSON, loadable in Perfetto / chrome://tracing.
- ``startup`` — a worker's start-up as eight exclusive stages, with JAX's
  own trace / lower / compile / cache-load events as spans and counters
  beneath them; buffers until the worker's tracer exists.
- ``snapshot`` — periodic atomic JSON snapshots for live inspection.
- ``clocksync`` — NTP-style per-worker clock-offset estimation from the
  heartbeat's four timestamps (median-of-window + drift tracking).
- ``timeline`` — merged cluster timeline: per-process events rebased onto
  the master clock by the estimated offsets, pids deduplicated.
- ``validate`` — trace-invariant checker backing scripts/validate_trace.py.

``get_registry()`` returns the process-global registry used by
process-scoped subsystems (the render path, ``ops/assignment``).
Cluster components that can be colocated in one process (the
harness runs a master and N workers on one loop) create their OWN
instances so per-component views stay separable.
"""

from __future__ import annotations

from tpu_render_cluster.obs.clocksync import ClockOffsetEstimator
from tpu_render_cluster.obs.flightrec import (
    FlightRecorder,
    resolve_flight_directory,
)
from tpu_render_cluster.obs.history import HistorySampler, HistoryStore
from tpu_render_cluster.obs.loopmon import LoopLagMonitor
from tpu_render_cluster.obs.registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_buckets,
    merge_wire,
)
from tpu_render_cluster.obs.snapshot import SnapshotWriter, write_metrics_snapshot
from tpu_render_cluster.obs.startup import STARTUP_STAGES, get_startup
from tpu_render_cluster.obs.timeline import (
    TimelineProcess,
    export_cluster_trace,
    merge_timeline,
    tracer_process,
)
from tpu_render_cluster.obs.tracer import (
    CPU_TIMED_STEPS,
    FILE_WRITE_OPS,
    FRAME_STEPS,
    Tracer,
    export_chrome_trace,
    frame_steps,
    step,
)
from tpu_render_cluster.obs.validate import (
    validate_trace_document,
    validate_trace_file,
)

__all__ = [
    "CPU_TIMED_STEPS",
    "DEFAULT_BUCKETS",
    "FILE_WRITE_OPS",
    "FRAME_STEPS",
    "ClockOffsetEstimator",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "HistorySampler",
    "HistoryStore",
    "LoopLagMonitor",
    "MetricsRegistry",
    "STARTUP_STAGES",
    "SnapshotWriter",
    "TimelineProcess",
    "Tracer",
    "export_chrome_trace",
    "export_cluster_trace",
    "frame_steps",
    "get_registry",
    "get_startup",
    "log_buckets",
    "merge_timeline",
    "merge_wire",
    "render_compile_counter",
    "resolve_flight_directory",
    "step",
    "tracer_process",
    "validate_trace_document",
    "validate_trace_file",
    "write_metrics_snapshot",
]

_global_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-global registry (render path, ops)."""
    return _global_registry


def render_compile_counter(registry: MetricsRegistry | None = None) -> Counter:
    """Render programs built by this process: the renderer factories
    (render/integrator.py, parallel/sharded_render.py) count the miss of
    their own ``lru_cache``, so it grows with configs, not frames."""
    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_compiles_total",
        "Render programs built (first sighting of a scene/shape/config by "
        "a renderer factory) — grows with configs, not frames",
    )
