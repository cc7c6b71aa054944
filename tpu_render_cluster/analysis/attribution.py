"""Whole-stack time attribution: where did the wall time go?

Folds the independently-collected timing evidence — the frames'
``device_wait`` step seconds (worker/queue.py), per-worker busy/idle
windows from the merged cluster timeline (analysis/critical_path.py), scheduler tick
phases (sched/tickprof.py), event-loop lag (obs/loopmon.py), and wire
serialize costs (transport/wirecost.py) — into ONE partition of the
run's worker-seconds:

- ``device_compute`` — seconds a frame's host thread was blocked on the
  device (the measured ``device_wait`` step, capped by worker busy time);
- ``host_glue`` — worker busy time that was NOT device execute: Python
  driving, image encode, file IO, backend overhead;
- ``transport`` — control-plane JSON serialize/parse seconds on both
  socket ends;
- ``control_plane`` — scheduler tick seconds (share scan, fair-share,
  pricing, dispatch);
- ``queue_wait`` — worker idle: no unit queued, the residual.

The partition is residual-based and therefore sums to exactly 1.0 by
construction: device is carved out of busy time, transport and control
out of what remains, and the residual splits into queue wait (up to the
measured idle) and host glue. Each component is a *measured lower bound*
clamped so overlapping instrumentation (a tick that runs while a worker
renders) can never push the total past the denominator.

``summarize_attribution`` (analysis/obs_events.py) extracts the inputs
from exported artifacts and calls :func:`attribution_report`.
"""

from __future__ import annotations

from typing import Any

__all__ = ["attribution_report", "FRACTION_KEYS"]

FRACTION_KEYS = (
    "device_compute",
    "host_glue",
    "queue_wait",
    "transport",
    "control_plane",
)


def _pool_from_sections(sections: dict[str, Any]) -> tuple[float, float]:
    """Total (busy_s, idle_s) across every run section's workers."""
    busy = idle = 0.0
    for section in sections.values():
        for worker in (section.get("workers") or {}).values():
            busy += float(worker.get("busy_s", 0.0))
            idle += float(worker.get("idle_s", 0.0))
    return busy, idle


def _partition(
    total: float,
    busy: float,
    idle: float,
    device_seconds: float,
    transport_seconds: float,
    control_seconds: float,
) -> dict[str, float]:
    """Carve ``total`` into the five components; sums to ``total`` exactly."""
    device = min(max(0.0, device_seconds), busy, total)
    remainder = total - device
    transport = min(max(0.0, transport_seconds), remainder)
    remainder -= transport
    control = min(max(0.0, control_seconds), remainder)
    remainder -= control
    queue_wait = min(max(0.0, idle), remainder)
    host_glue = remainder - queue_wait
    return {
        "device_compute": device,
        "host_glue": host_glue,
        "queue_wait": queue_wait,
        "transport": transport,
        "control_plane": control,
    }


def attribution_report(
    *,
    critical_sections: dict[str, Any] | None = None,
    worker_seconds: float | None = None,
    device_seconds: float = 0.0,
    transport_seconds: float = 0.0,
    control_seconds: float = 0.0,
    tick: dict[str, Any] | None = None,
    loop_lag: dict[str, Any] | None = None,
    top_talkers: list[dict[str, Any]] | None = None,
) -> dict[str, Any] | None:
    """Build the ``attribution`` section.

    The denominator is the run's total worker-seconds: summed per-worker
    ``busy_s + idle_s`` from ``critical_sections`` (the per-run
    ``summarize_critical_path`` outputs) when a merged timeline exists,
    else the explicit ``worker_seconds`` window (bench: elapsed x
    workers). None when neither yields a positive denominator.
    """
    busy = idle = 0.0
    if critical_sections:
        busy, idle = _pool_from_sections(critical_sections)
    total = busy + idle
    if total <= 0.0 and worker_seconds is not None:
        total = max(0.0, float(worker_seconds))
        busy, idle = total, 0.0
    if total <= 0.0:
        return None

    seconds = _partition(
        total, busy, idle, device_seconds, transport_seconds, control_seconds
    )
    fractions = {key: value / total for key, value in seconds.items()}
    out: dict[str, Any] = {
        "worker_seconds": round(total, 6),
        "seconds": {k: round(v, 6) for k, v in seconds.items()},
        "fractions": {k: round(v, 6) for k, v in fractions.items()},
        "fractions_sum": round(sum(fractions.values()), 6),
    }
    if tick:
        out["tick"] = tick
    if loop_lag:
        out["loop_lag"] = loop_lag
    if top_talkers:
        out["top_talkers"] = top_talkers

    if critical_sections and busy + idle > 0.0:
        # Per-run (per-job in the harness's one-trace-per-job naming):
        # device splits by each run's share of busy time, transport and
        # control-plane by its share of the total window — the master's
        # costs serve every job concurrently, so a wall-time share is
        # the fairest apportioning the evidence supports.
        per_run: dict[str, Any] = {}
        for stem, section in critical_sections.items():
            run_busy, run_idle = _pool_from_sections({stem: section})
            run_total = run_busy + run_idle
            if run_total <= 0.0:
                continue
            run_device = device_seconds * (run_busy / busy) if busy else 0.0
            run_transport = transport_seconds * (run_total / total)
            run_control = control_seconds * (run_total / total)
            run_seconds = _partition(
                run_total, run_busy, run_idle,
                run_device, run_transport, run_control,
            )
            per_run[stem] = {
                "worker_seconds": round(run_total, 6),
                "fractions": {
                    k: round(v / run_total, 6) for k, v in run_seconds.items()
                },
            }
        if per_run:
            out["per_run"] = per_run
    return out
