"""Scheduler tick phase profiler.

ROADMAP item 3 asserts the sched tick's share scan and per-dispatch JSON
payloads dominate the control-plane profile; this module produces the
committed profile that claim (and any incremental-WFQ rewrite beating
it) is measured against. ``JobManager._scheduler_loop`` brackets each
tick with ``begin_tick``/``end_tick`` and wraps its phases — cost-model
``pricing``, ``share_scan``, ``fair_share`` pick, ``dispatch``,
``preempt``, ``speculation`` — in ``phase()`` contexts. Each phase and
the whole tick feed the ``sched_tick_seconds{phase}`` histogram
(``phase="total"`` for the tick) and draw spans on a dedicated "sched"
Perfetto track; ``sched_tick_budget_ratio`` is a rolling gauge of mean
tick time over the configured tick budget (``> 1`` means the loop can
no longer hold its cadence). A pass that a worker event started before
the tick was due (master/wakeup.py) records its phases like any other but
its whole under ``phase="event_total"``, draws an ``sched event pass``
span, and stays out of ``phase="total"``, the tick count and the budget
gauge: those keep meaning the timer's ticks against the timer's budget.

The dispatch RPC round-trip and the queue-add JSON serialize happen off
the tick's critical section (inside ``WorkerHandle``), so those sites
report through :func:`observe_dispatch_phase` instead — same histogram,
phases ``dispatch_rpc_await`` / ``dispatch_serialize`` — keeping the
metric name owned here.

``TRC_SCHED_PROFILE=0`` disables recording (consulted per tick, so
tests and long-lived processes can flip it live).
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager

from tpu_render_cluster.utils.env import env_str

__all__ = [
    "TickProfiler",
    "observe_dispatch_phase",
    "LOOP_PHASES",
    "TICK_METRIC",
    "BUDGET_METRIC",
]

TICK_METRIC = "sched_tick_seconds"
BUDGET_METRIC = "sched_tick_budget_ratio"

_TICK_HELP = "Scheduler tick time by phase (phase=total covers the whole tick)"
_BUDGET_HELP = "Rolling mean tick time over the tick budget (>1 = overrun)"

# Phases recorded INSIDE one tick's begin/end bracket; their per-tick sum
# is bounded by the tick's phase="total" wall time (the phase-sum test).
LOOP_PHASES = (
    "pricing",
    "share_scan",
    "fair_share",
    "dispatch",
    "preempt",
    "speculation",
)

# Ticks folded into the rolling budget gauge.
BUDGET_WINDOW = 32


def profiling_enabled() -> bool:
    return (env_str("TRC_SCHED_PROFILE", "1") or "").strip() not in ("0", "off")


class TickProfiler:
    """Per-tick phase timing for one scheduler loop."""

    def __init__(
        self,
        metrics,
        span_tracer=None,
        *,
        tick_budget_seconds: float = 0.05,
        flightrec=None,
    ) -> None:
        self.metrics = metrics
        self.span_tracer = span_tracer
        self.tick_budget_seconds = max(1e-9, tick_budget_seconds)
        self.ticks = 0
        self._hist = metrics.histogram(TICK_METRIC, _TICK_HELP, labels=("phase",))
        self._budget = metrics.gauge(BUDGET_METRIC, _BUDGET_HELP)
        self._totals: deque[float] = deque(maxlen=BUDGET_WINDOW)
        self._tick_active = False
        self._event_woken = False
        self._tick_start_wall = 0.0
        self._tick_start = 0.0
        # Flight-recorder seam (obs/flightrec.py): a rolling budget ratio
        # crossing 1.0 dumps a black box, like loop_lag does. Edge-
        # triggered here (fire on the below->above crossing, re-arm on
        # dropping back under) on top of the recorder's own per-kind
        # debounce, so a sustained overrun is one dump, not one per tick.
        self.flightrec = flightrec
        self._over_budget = False

    def begin_tick(self, *, event_woken: bool = False) -> None:
        self._tick_active = profiling_enabled()
        self._event_woken = event_woken
        if not self._tick_active:
            return
        self._tick_start_wall = time.time()
        self._tick_start = time.perf_counter()

    @contextmanager
    def phase(self, name: str):
        if not self._tick_active:
            yield
            return
        start_wall = time.time()
        started = time.perf_counter()
        try:
            yield
        finally:
            elapsed = time.perf_counter() - started
            self._hist.observe(elapsed, phase=name)
            if self.span_tracer is not None:
                self.span_tracer.complete(
                    name,
                    cat="sched",
                    start_wall=start_wall,
                    duration=elapsed,
                    track="sched",
                )

    def end_tick(self) -> None:
        if not self._tick_active:
            return
        self._tick_active = False
        total = time.perf_counter() - self._tick_start
        if self._event_woken:
            # A pass a worker's event started early: timed apart, outside
            # the tick count and the budget (the timer's, both of them).
            phase, span = "event_total", "sched event pass"
        else:
            phase, span = "total", "sched tick"
            self.ticks += 1
            self._observe_budget(total)
        self._hist.observe(total, phase=phase)
        if self.span_tracer is not None:
            self.span_tracer.complete(
                span,
                cat="sched",
                start_wall=self._tick_start_wall,
                duration=total,
                track="sched",
                args={"tick": self.ticks},
            )

    def _observe_budget(self, total: float) -> None:
        self._totals.append(total)
        ratio = sum(self._totals) / len(self._totals) / self.tick_budget_seconds
        self._budget.set(ratio)
        if self.flightrec is None:
            return
        if ratio <= 1.0:
            self._over_budget = False
        elif not self._over_budget:
            self._over_budget = True
            from tpu_render_cluster.obs.flightrec import TRIGGER_TICK_BUDGET

            self.flightrec.trigger(
                TRIGGER_TICK_BUDGET,
                {
                    "budget_ratio": round(ratio, 4),
                    "tick_budget_seconds": self.tick_budget_seconds,
                    "last_tick_seconds": round(total, 6),
                    "ticks": self.ticks,
                },
            )


def observe_dispatch_phase(metrics, phase: str, seconds: float) -> None:
    """Record an off-tick dispatch cost into ``sched_tick_seconds``.

    Used by the master's per-worker handles for ``dispatch_rpc_await``
    (queue-add send -> ack) and ``dispatch_serialize`` (queue-add JSON
    encode); no-op when profiling is off or no registry is wired.
    """
    if metrics is None or not profiling_enabled():
        return
    metrics.histogram(TICK_METRIC, _TICK_HELP, labels=("phase",)).observe(
        seconds, phase=phase
    )
