"""Sampled asyncio event-loop lag probe.

The static trc-lint ``loop-blocking`` pass proves no *statically
resolvable* sync call parks the loop; this is the runtime complement —
it measures how late the loop actually runs scheduled callbacks. The
probe sleeps ``TRC_OBS_LOOPMON_INTERVAL`` seconds and compares the
monotonic wake time against the scheduled one: the delta is exactly the
time some other callback held the loop (GC pauses, an unexpectedly-sync
hot path, a compiler sneaking onto the loop). Each sample feeds the
``obs_loop_lag_seconds{role}`` histogram; samples over
``TRC_OBS_LOOPMON_THRESHOLD`` count a blocked episode
(``obs_loop_blocked_episodes_total{role}``), draw a span on the "loop"
Perfetto track covering the blocked window, and — when a flight
recorder is attached — dump a ``loop_lag`` blackbox bundle (debounced
by the recorder's existing ``TRC_OBS_FLIGHT_DEBOUNCE`` machinery).

Each sample also reads what the PROCESS was doing meanwhile: its CPU
clock (``time.process_time``, every thread of it) and, where the host
lets it be read, ``/proc/self/schedstat`` — the main thread's, which is
the loop's own; its second field is the nanoseconds the thread was
runnable and not run. A blocked episode's lag is counted in
``obs_loop_blocked_seconds_total{role,cause}`` under exactly one of
``BLOCKED_CAUSES`` (``blocked_cause``), and its span says both readings.

One monitor per process role: the master (``role="master"``), each
worker runtime (``"worker"``), and the shard router (``"router"``).
"""

from __future__ import annotations

import asyncio
import logging
import time

from tpu_render_cluster.utils.env import env_float

__all__ = [
    "BLOCKED_CAUSES",
    "BLOCKED_SECONDS_METRIC",
    "EPISODES_METRIC",
    "LAG_METRIC",
    "LoopLagMonitor",
    "blocked_cause",
]

logger = logging.getLogger(__name__)

LAG_METRIC = "obs_loop_lag_seconds"
EPISODES_METRIC = "obs_loop_blocked_episodes_total"

BLOCKED_SECONDS_METRIC = "obs_loop_blocked_seconds_total"

_LAG_HELP = "Event-loop callback lag (scheduled vs actual wake) by role"
_EPISODES_HELP = "Loop-lag samples over TRC_OBS_LOOPMON_THRESHOLD by role"
_BLOCKED_SECONDS_HELP = (
    "Lag of the blocked episodes by role and by what the process was doing "
    "over the late sample (not_scheduled/process_busy/process_idle)"
)

# Why a sample came late, from what the process did between the sample
# before it and itself; the first that holds:
#   not_scheduled  the loop's thread stood on a run queue for at least half
#                  the lag: runnable, and given no CPU (the host took it).
#                  Never where schedstat cannot be read
#   process_busy   the process used at least half a CPU over the sample:
#                  threads of ours (an encoder that holds the GIL, several
#                  savers) crowded the loop out
#   process_idle   nobody of ours ran and the loop was not runnable: the
#                  whole process stood in a call, or was stopped
BLOCKED_CAUSES = ("not_scheduled", "process_busy", "process_idle")

SCHEDSTAT_PATH = "/proc/self/schedstat"


def blocked_cause(
    lag: float, elapsed: float, process_cpu_s: float, run_delay_s: float | None
) -> str:
    """Which of ``BLOCKED_CAUSES`` a sample that came ``lag`` seconds late
    falls under: ``elapsed`` seconds after the one before it, in which
    the process ran ``process_cpu_s`` CPU seconds and the loop's thread
    waited ``run_delay_s`` for a CPU (None: not readable here)."""
    if run_delay_s is not None and run_delay_s >= lag / 2.0:
        return "not_scheduled"
    if process_cpu_s >= elapsed / 2.0:
        return "process_busy"
    return "process_idle"


def read_schedstat() -> str | None:
    """The text of this process's schedstat, which is its main thread's;
    None on a host that does not show it."""
    try:
        # trc-lint: disable=loop-blocking (procfs: the kernel fills some forty bytes from memory, no device to wait for)
        with open(SCHEDSTAT_PATH, encoding="ascii") as schedstat:
            return schedstat.read()
    except (OSError, ValueError):
        return None


def run_delay_seconds(schedstat: str | None) -> float | None:
    """Seconds the thread has been runnable and not run: the second field
    of its schedstat, in nanoseconds there; None where there is none."""
    try:
        return int(schedstat.split()[1]) / 1e9
    except (AttributeError, ValueError, IndexError):
        return None


def loopmon_interval_seconds() -> float:
    return max(0.001, env_float("TRC_OBS_LOOPMON_INTERVAL", 0.25))


def loopmon_threshold_seconds() -> float:
    return max(0.0, env_float("TRC_OBS_LOOPMON_THRESHOLD", 0.1))


class LoopLagMonitor:
    """Periodic lag sampler for the current event loop.

    ``start()`` inside a running loop; ``await stop()`` on teardown.
    The span tracer and flight recorder are optional — workers run with
    just the histogram, the master wires all three.
    """

    def __init__(
        self,
        metrics,
        *,
        role: str,
        span_tracer=None,
        flightrec=None,
    ) -> None:
        self.metrics = metrics
        self.role = role
        self.span_tracer = span_tracer
        self.flightrec = flightrec
        self.samples = 0
        self.blocked_episodes = 0
        self.max_lag_seconds = 0.0
        self._lag = metrics.histogram(LAG_METRIC, _LAG_HELP, labels=("role",))
        self._episodes = metrics.counter(
            EPISODES_METRIC, _EPISODES_HELP, labels=("role",)
        )
        # The role's series is exposed from the start, at 0: a scrape that
        # finds no series cannot tell "never blocked" from "not monitored".
        self._episodes.inc(0.0, role=role)
        self._blocked_seconds = metrics.counter(
            BLOCKED_SECONDS_METRIC, _BLOCKED_SECONDS_HELP, labels=("role", "cause")
        )
        for cause in BLOCKED_CAUSES:
            self._blocked_seconds.inc(0.0, role=role, cause=cause)
        # The clock and the process's readings, replaced by a test that
        # injects its own (the clock is the default loop's own).
        self.clock = time.monotonic
        self.process_time = time.process_time
        self.read_schedstat = read_schedstat
        self._task: asyncio.Task | None = None

    def start(self) -> None:
        if run_delay_seconds(self.read_schedstat()) is None:
            # probed once: a host that hides schedstat is not asked again
            self.read_schedstat = lambda: None
        if self._task is None or self._task.done():
            self._task = asyncio.create_task(
                self._run(), name=f"loopmon-{self.role}"
            )

    async def stop(self) -> None:
        if self._task is None:
            return
        self._task.cancel()
        try:
            await self._task
        except asyncio.CancelledError:
            pass
        self._task = None

    async def _run(self) -> None:
        while True:
            interval = loopmon_interval_seconds()
            started = self.clock()
            cpu_before = self.process_time()
            delay_before = run_delay_seconds(self.read_schedstat())
            await asyncio.sleep(interval)
            woke = self.clock()
            lag = max(0.0, woke - started - interval)
            self.samples += 1
            self.max_lag_seconds = max(self.max_lag_seconds, lag)
            self._lag.observe(lag, role=self.role)
            if lag >= loopmon_threshold_seconds():
                delay_after = run_delay_seconds(self.read_schedstat())
                self._record_episode(
                    lag,
                    elapsed=woke - started,
                    process_cpu_s=self.process_time() - cpu_before,
                    run_delay_s=(
                        None if delay_before is None or delay_after is None
                        else delay_after - delay_before
                    ),
                )

    def _record_episode(
        self, lag: float, *, elapsed: float, process_cpu_s: float, run_delay_s: float | None
    ) -> None:
        cause = blocked_cause(lag, elapsed, process_cpu_s, run_delay_s)
        self.blocked_episodes += 1
        self._episodes.inc(role=self.role)
        self._blocked_seconds.inc(lag, role=self.role, cause=cause)
        logger.warning(
            "Event loop (%s) blocked ~%.3fs (threshold %.3fs): %s "
            "(process CPU %.3fs, run-queue delay %s).",
            self.role, lag, loopmon_threshold_seconds(), cause, process_cpu_s,
            "unreadable" if run_delay_s is None else f"{run_delay_s:.3f}s",
        )
        if self.span_tracer is not None:
            # The lag window ends at the sample; anchor the span so it
            # covers the time the loop was held.
            self.span_tracer.complete(
                "loop blocked",
                cat="obs",
                start_wall=time.time() - lag,
                duration=lag,
                track="loop",
                args={
                    "role": self.role,
                    "lag_s": round(lag, 6),
                    "cause": cause,
                    "process_cpu_s": round(process_cpu_s, 6),
                    "run_delay_s": None if run_delay_s is None else round(run_delay_s, 6),
                },
            )
        if self.flightrec is not None:
            from tpu_render_cluster.obs.flightrec import TRIGGER_LOOP_LAG

            self.flightrec.trigger(
                TRIGGER_LOOP_LAG,
                {
                    "role": self.role,
                    "lag_seconds": round(lag, 6),
                    "threshold_seconds": loopmon_threshold_seconds(),
                },
            )
