"""Always-on flight recorder: the last N seconds, dumped on incident.

The post-mortem half of the continuous-observability layer. While the
process runs, the recorder costs almost nothing — it *references* the
bounded state other obs components already keep (the history store's
sample ring, the span tracer's event buffer) and maintains one small
deque of protocol-event digests of its own. When something goes wrong it
dumps an atomic blackbox bundle covering the window *leading up to* the
incident — the data that is otherwise already gone by the time anyone
scrapes ``/metrics``.

Trigger seams (wired in master/cluster.py and sched/manager.py):

- ``slo_alert`` — an SLO alert FIRE edge (obs/slo.py ``on_alert``);
- ``worker_eviction`` — a worker marked dead and evicted;
- ``job_failure`` — a job cancelled for a deterministic unit failure
  (``state.failed_reason``);
- ``epoch_fence`` — a worker event refused for echoing a previous master
  incarnation's epoch;
- ``master_failover`` — this incarnation adopted a predecessor's ledger.

Bundle format: a Chrome trace-event document (``traceEvents`` at the top
level, so ``scripts/validate_trace.py`` and Perfetto both load it
directly) plus a ``blackbox`` section carrying the trigger, the sample
window, the history store's metric samples, the protocol-event digests,
and a final registry snapshot. Only complete (``X``), instant (``i``),
and metadata events are included — flow/duration events whose
counterparts fall outside the window would fail the trace validator, and
a blackbox that fails validation is worse than one without arrows.

Dumps are debounced per trigger kind (``TRC_OBS_FLIGHT_DEBOUNCE``): an
eviction storm produces one bundle per kind per window, not hundreds.
Every ACTUAL dump is counted in ``obs_flight_dumps_total{trigger}``.

Tuning: ``TRC_OBS_FLIGHT_SECONDS`` (window, default 60),
``TRC_OBS_FLIGHT_EVENTS`` (protocol-digest ring size),
``TRC_OBS_FLIGHT_DEBOUNCE`` (seconds between dumps per trigger),
``TRC_OBS_FLIGHT_DIR`` (dump directory; without one — explicit, env, or
derived from the metrics snapshot path — triggers are still counted and
recorded in ``view()`` but no file is written).
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import os
import threading
import time
from collections import deque
from pathlib import Path
from typing import TYPE_CHECKING, Any

from tpu_render_cluster.utils.background import BackgroundTasks
from tpu_render_cluster.utils.env import env_float, env_str

if TYPE_CHECKING:
    from tpu_render_cluster.obs.history import HistoryStore
    from tpu_render_cluster.obs.registry import MetricsRegistry
    from tpu_render_cluster.obs.tracer import Tracer

logger = logging.getLogger(__name__)

__all__ = ["FlightRecorder", "resolve_flight_directory"]

TRIGGER_SLO_ALERT = "slo_alert"
TRIGGER_WORKER_EVICTION = "worker_eviction"
TRIGGER_JOB_FAILURE = "job_failure"
TRIGGER_EPOCH_FENCE = "epoch_fence"
TRIGGER_MASTER_FAILOVER = "master_failover"
TRIGGER_PROMOTION = "promotion"
TRIGGER_LOOP_LAG = "loop_lag"
TRIGGER_TICK_BUDGET = "tick_budget"


def flight_window_seconds() -> float:
    return max(1.0, env_float("TRC_OBS_FLIGHT_SECONDS", 60.0))


def flight_debounce_seconds() -> float:
    return max(0.0, env_float("TRC_OBS_FLIGHT_DEBOUNCE", 5.0))


def flight_max_events() -> int:
    return max(16, int(env_float("TRC_OBS_FLIGHT_EVENTS", 4096)))


def resolve_flight_directory(
    explicit: str | Path | None, fallback: str | Path | None = None
) -> Path | None:
    """Explicit argument wins, else ``TRC_OBS_FLIGHT_DIR``, else the
    caller's fallback (the metrics snapshot's directory), else None."""
    if explicit is not None:
        return Path(explicit)
    env = env_str("TRC_OBS_FLIGHT_DIR")
    if env:
        return Path(env)
    if fallback is not None:
        return Path(fallback)
    return None


class FlightRecorder:
    """One process's blackbox: bounded recent context + triggered dumps."""

    def __init__(
        self,
        *,
        history: "HistoryStore | None" = None,
        span_tracer: "Tracer | None" = None,
        metrics: "MetricsRegistry | None" = None,
        directory: str | Path | None = None,
        window_seconds: float | None = None,
        process_name: str = "master",
    ) -> None:
        self.history = history
        self.span_tracer = span_tracer
        self.metrics = metrics
        self.directory = Path(directory) if directory is not None else None
        self.window_seconds = (
            window_seconds if window_seconds is not None else flight_window_seconds()
        )
        self.process_name = process_name
        self._lock = threading.Lock()
        self._events: deque[tuple[float, str, dict[str, Any]]] = deque(
            maxlen=flight_max_events()
        )
        self._last_dump: dict[str, float] = {}
        self._sequence = 0
        # Every trigger attempt (incl. debounced) and every actual dump.
        # The dump ledger is bounded like SloService.alerts: a long-lived
        # service with recurring incidents must not grow it (or the
        # /clusterz view serializing it) without limit; the counter keeps
        # the lifetime totals.
        self.triggers: dict[str, int] = {}
        self.dumps: deque[dict[str, Any]] = deque(maxlen=256)
        # Deferred bundle writes in flight (loop contexts only).
        self._pending = BackgroundTasks()
        self._last_write_ok = True

    # -- recording -----------------------------------------------------------

    def record_event(self, kind: str, **detail: Any) -> None:
        """One protocol-event digest (dispatch, finished, refusal, ...):
        cheap enough for the master's hottest paths — a deque append."""
        self._events.append((time.time(), str(kind), detail))

    # -- triggering ----------------------------------------------------------

    def trigger(
        self, trigger: str, detail: dict[str, Any] | None = None
    ) -> Path | None:
        """Dump a blackbox bundle for ``trigger`` (debounced per kind).

        Returns the bundle path, or None when debounced / no directory is
        configured (the trigger is still counted and recorded either way).
        """
        now = time.time()
        with self._lock:
            self.triggers[trigger] = self.triggers.get(trigger, 0) + 1
            last = self._last_dump.get(trigger, -math.inf)
            if now - last < flight_debounce_seconds():
                return None
            self._last_dump[trigger] = now
            self._sequence += 1
            sequence = self._sequence
        bundle = self._build_bundle(trigger, detail or {}, now)
        path: Path | None = None
        if self.directory is not None:
            path = (
                self.directory
                / f"{self.process_name}-{sequence:03d}-{trigger}_blackbox.json"
            )
            if not self._dispatch_write(path, bundle):
                path = None
        record = {
            "trigger": trigger,
            "at": now,
            "window": bundle["blackbox"]["window"],
            "path": str(path) if path is not None else None,
        }
        with self._lock:
            self.dumps.append(record)
        if self.metrics is not None:
            self.metrics.counter(
                "obs_flight_dumps_total",
                "Flight-recorder blackbox bundles dumped, by trigger",
                labels=("trigger",),
            ).inc(trigger=trigger)
        if self.span_tracer is not None:
            self.span_tracer.instant(
                f"flight dump {trigger}",
                cat="flight",
                track="flights",
                args={"trigger": trigger, **(detail or {})},
            )
        logger.warning(
            "Flight recorder dumped (%s): %s", trigger, path or "<in-memory>"
        )
        return path

    # -- bundle assembly -----------------------------------------------------

    def _build_bundle(
        self, trigger: str, detail: dict[str, Any], now: float
    ) -> dict[str, Any]:
        t0 = now - self.window_seconds
        trace_events: list[dict[str, Any]] = []
        if self.span_tracer is not None:
            trace_events.extend(self.span_tracer.metadata_events())
            t0_us, now_us = t0 * 1e6, now * 1e6
            for event in self.span_tracer.events():
                ph = event.get("ph")
                ts = event.get("ts")
                if not isinstance(ts, (int, float)):
                    continue
                if ph == "X":
                    # Include spans OVERLAPPING the window (a long-running
                    # job span that started before it still matters).
                    if ts <= now_us and ts + float(event.get("dur", 0)) >= t0_us:
                        trace_events.append(event)
                elif ph == "i" and t0_us <= ts <= now_us:
                    trace_events.append(event)
                # B/E and flow events are dropped: their counterparts may
                # fall outside the cut and the bundle must validate clean.
        # Bounded on BOTH edges: the sampler thread runs concurrently with
        # this build, so a sample stamped just after `now` would otherwise
        # land in the bundle outside its declared window and fail the
        # blackbox validator.
        samples = (
            [s for s in self.history.samples_since(t0) if s["t"] <= now]
            if self.history is not None
            else []
        )
        protocol_events = [
            {"t": t, "kind": kind, **digest}
            for t, kind, digest in list(self._events)
            if t0 <= t <= now
        ]
        blackbox: dict[str, Any] = {
            "trigger": trigger,
            "detail": detail,
            "process": self.process_name,
            "dumped_at": now,
            "window": [t0, now],
            "metric_samples": samples,
            "protocol_events": protocol_events,
        }
        if self.history is not None:
            blackbox["history_meta"] = self.history.meta()
        if self.metrics is not None:
            blackbox["final_metrics"] = self.metrics.snapshot()
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {"blackbox_trigger": trigger},
            "blackbox": blackbox,
        }

    def _dispatch_write(self, path: Path, bundle: dict[str, Any]) -> bool:
        """Write the bundle WITHOUT ever holding an event loop.

        The triggers fire inside the master's async handlers (SLO fires,
        evictions, epoch-fence refusals), where the serialize+fsync of a
        multi-megabyte bundle would stall heartbeat service exactly when
        the cluster is already in trouble. On a running loop the atomic
        write is deferred to ``asyncio.to_thread`` (tracked; ``drain()``
        awaits it at shutdown so no bundle is lost to loop teardown).
        Without a loop the write still runs on a short-lived worker
        thread — structurally, ``_write_atomic`` cannot execute on a
        thread that owns a running event loop, which is also what keeps
        the loop-blocking lint clean without suppressions.

        Returns False only on a synchronous write failure; deferred
        failures are logged by the writer task (the recorded ``path`` of
        such a dump may then name a file that never landed — the log
        line and the bundle's absence are the post-mortem's post-mortem).
        """
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is not None:
            self._pending.spawn(
                asyncio.to_thread(self._write_checked, path, bundle),
                name=f"flightrec-dump-{path.name}",
            )
            return True
        worker = threading.Thread(
            target=self._write_checked, args=(path, bundle), daemon=True
        )
        worker.start()
        worker.join()
        return self._last_write_ok

    def _write_checked(self, path: Path, bundle: dict[str, Any]) -> None:
        try:
            self._write_atomic(path, bundle)
            self._last_write_ok = True
        except OSError as e:
            self._last_write_ok = False
            logger.error("Flight-recorder dump to %s failed: %s", path, e)

    async def drain(self) -> None:
        """Await every deferred bundle write (call before loop teardown):
        none is pending on return, and the wait always yields to the loop."""
        await self._pending.drain()

    @staticmethod
    def _write_atomic(path: Path, bundle: dict[str, Any]) -> None:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(path.suffix + ".tmp")
        with open(tmp, "w", encoding="utf-8") as handle:
            json.dump(bundle, handle)
            handle.flush()
            os.fsync(handle.fileno())
        os.replace(tmp, path)

    # -- views ---------------------------------------------------------------

    def view(self) -> dict[str, Any]:
        with self._lock:
            return {
                "window_seconds": self.window_seconds,
                "directory": str(self.directory) if self.directory else None,
                "triggers": dict(self.triggers),
                "dumps": list(self.dumps),
            }
