"""The multi-job scheduler: admission, fair-share dispatch, preemption.

``JobManager`` turns the master into a long-running service. It reuses
the whole single-job stack — the accepting server, 3-step handshake,
heartbeats, worker handles, eviction, drain, the exactly-once result
ledger — by subclassing ``ClusterManager`` in SERVICE mode (``job=None``)
and overriding the two multi-job hooks:

- ``_state_for_job``: worker events route to the owning job's frame table
  by the reference ``job_name`` field every event already carries (so C++
  workers that echo no ``job_id`` piggyback still route correctly);
- ``_active_job_announcements`` / ``_announce_active_jobs``: late-joining
  workers get one ``event_job-started`` replay per ACTIVE job.

Scheduling model (sched/fair_share.py): jobs are admitted from a queue
(priority order, capped by ``TRC_SCHED_MAX_ACTIVE_JOBS`` and each job's
worker barrier), then one dispatch loop multiplexes every running job
over the shared worker pool — per tick, each worker below its target
queue size receives the next frame of the runnable job with the smallest
``in_flight / weight`` (weighted fair queueing), and an over-share job is
preempted (its newest not-yet-rendering frame unqueued back to its own
pending pool, via the same frame-queue-remove RPC steals use) when
another job is starved by at least a whole slot.

A worker whose socket is lost is SILENT for the reconnect window
(``WorkerHandle.is_silent``): live, its queue kept, handed nothing new.
A pass waits on no worker, silent or slow: announcements, queue-adds and
a preemption's unqueue go out on tasks beside the loop, and a pass picks
among ``serving_workers()`` (the cancel of a job that failed is the one
RPC the loop still awaits, of serving workers alone).

Lifecycle API (``submit`` / ``job_status`` / ``cancel_job`` /
``request_drain``) is exposed over a JSON-lines control socket
(sched/control.py) consumed by ``python -m tpu_render_cluster.sched.submit``
and the master CLI's ``serve`` subcommand.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any

from tpu_render_cluster.master.cluster import ClusterManager
from tpu_render_cluster.master.assembly import cut_writes_counter
from tpu_render_cluster.master.state import (
    HANDBACK_CAUSES,
    ClusterManagerState,
    FrameStatus,
)
from tpu_render_cluster.master.strategies import (
    claim_is_to_return,
    claim_pending_unit,
    preempt_frame,
    send_claimed_unit,
)
from tpu_render_cluster.master.worker_handle import (
    WorkerHandle,
    evictions_counter,
    rendered_twice_counter,
    silent_seconds_counter,
)
from tpu_render_cluster.obs import MetricsRegistry, Tracer
from tpu_render_cluster.sched import fair_share
from tpu_render_cluster.sched.tickprof import TickProfiler, observe_dispatch_phase
from tpu_render_cluster.sched.models import (
    JOB_CANCELLED,
    JOB_FINISHED,
    JOB_QUEUED,
    JOB_RUNNING,
    JobRun,
    JobSpec,
)
from tpu_render_cluster.sched.wfq import IncrementalWFQ
from tpu_render_cluster.traces.worker_trace import WorkerTrace
from tpu_render_cluster.utils.background import BackgroundTasks
from tpu_render_cluster.utils.env import env_float, env_int, env_str

logger = logging.getLogger(__name__)

# ``status`` without a ``job_id`` lists every queued and running job and the
# newest this many ended ones: a service that has run for a day has ended
# tens of thousands, and a client that polls asks ten times a second.
ENDED_JOBS_LISTED = 256
# Buckets of ``sched_job_worker_units``: a count of workers, not of seconds.
JOB_WORKERS_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0, 48.0, 64.0, 96.0)


@dataclass(frozen=True)
class SchedulerConfig:
    """Tuning knobs, each with a ``TRC_SCHED_*`` environment override."""

    # Dispatch/admission tick. The single-job strategies tick at 50 ms
    # (reference: strategies.rs); the service loop matches. It is the
    # longest the loop waits between passes: a worker event that leaves a
    # queue shallow starts the next pass at once (master/wakeup.py).
    tick_seconds: float = 0.05
    # In-flight frame slots per live worker (the eager-naive-coarse
    # "target queue size" generalized to the whole service).
    target_queue_size: int = 2
    # Concurrently RUNNING jobs; further submissions wait in admission.
    max_active_jobs: int = 4
    # Master-side preemption of over-share jobs (fair_share.pick_preemption).
    preemption: bool = True
    max_preemptions_per_tick: int = 1
    # While DRAINING with nothing running, queued jobs whose worker
    # barrier exceeds the live pool are cancelled after this grace (late
    # worker connects get that long to satisfy the barrier); without it a
    # drained service would park forever on an unadmittable job.
    drain_barrier_grace_seconds: float = 10.0
    # Tick pick structure (sched/wfq.py): "heap" keeps per-job WFQ keys
    # in an incrementally synced priority queue (dispatch pick = heap
    # peek, share resync only for jobs whose state changed); "scan" is
    # the legacy full-rescan path kept as fallback and A/B baseline;
    # "verify" runs both and asserts every pick agrees (debug — it also
    # pins load metering to unit counts, the regime where heap-vs-scan
    # equivalence is exact rather than within the scan's tie tolerance).
    tick_mode: str = "heap"

    @classmethod
    def from_env(cls) -> "SchedulerConfig":
        tick_mode = (env_str("TRC_SCHED_TICK", cls.tick_mode) or "").strip()
        if tick_mode not in ("heap", "scan", "verify"):
            logger.warning(
                "Ignoring unknown TRC_SCHED_TICK=%r; using %r",
                tick_mode, cls.tick_mode,
            )
            tick_mode = cls.tick_mode
        return cls(
            tick_seconds=env_float("TRC_SCHED_TICK_SECONDS", cls.tick_seconds),
            target_queue_size=env_int(
                "TRC_SCHED_TARGET_QUEUE_SIZE", cls.target_queue_size
            ),
            max_active_jobs=env_int(
                "TRC_SCHED_MAX_ACTIVE_JOBS", cls.max_active_jobs
            ),
            preemption=env_int("TRC_SCHED_PREEMPTION", 1) != 0,
            max_preemptions_per_tick=env_int(
                "TRC_SCHED_MAX_PREEMPTIONS_PER_TICK", cls.max_preemptions_per_tick
            ),
            drain_barrier_grace_seconds=env_float(
                "TRC_SCHED_DRAIN_GRACE_SECONDS", cls.drain_barrier_grace_seconds
            ),
            tick_mode=tick_mode,
        )


class JobManager(ClusterManager):
    """Long-running multi-job master over one shared worker pool."""

    def __init__(
        self,
        host: str,
        port: int,
        *,
        config: SchedulerConfig | None = None,
        metrics: MetricsRegistry | None = None,
        span_tracer: Tracer | None = None,
        metrics_snapshot_path: str | Path | None = None,
        dispatch_delay_fn=None,
        output_base_directory: str | Path | None = None,
        telemetry_port: int | None = None,
        ledger=None,
    ) -> None:
        super().__init__(
            host,
            port,
            None,  # service mode: no single job, per-job states at admission
            metrics=metrics,
            span_tracer=span_tracer,
            metrics_snapshot_path=metrics_snapshot_path,
            dispatch_delay_fn=dispatch_delay_fn,
            output_base_directory=output_base_directory,
            telemetry_port=telemetry_port,
            ledger=ledger,
        )
        self.config = config if config is not None else SchedulerConfig.from_env()
        self.tickprof = TickProfiler(
            self.metrics,
            self.span_tracer,
            tick_budget_seconds=self.config.tick_seconds,
            flightrec=self.flightrec,
        )
        # Incremental WFQ pick structure (heap/verify tick modes): synced
        # per tick for DIRTY jobs only (state.version mismatch), so the
        # share_scan phase is O(changed jobs), not O(jobs x frames).
        self._wfq = IncrementalWFQ()
        self._runs: dict[str, JobRun] = {}  # job_id -> run, submit order
        self._admission: list[str] = []  # queued job_ids, submit order
        self._running: list[str] = []  # running job_ids, admission order
        self._active_by_name: dict[str, JobRun] = {}
        self._draining = False
        self._cancelling: set[str] = set()
        self._drain_stuck_since: float | None = None
        self._job_seq = 0
        self._started_serving = time.time()
        # the newest ENDED_JOBS_LISTED ended job_ids, oldest first
        self._ended: list[str] = []
        # What is asked of workers beside the loop (queue-adds, job
        # announcements, preemptions' unqueues): the tasks, per worker the
        # claimed units its mirror does not hold yet, and per worker the
        # over-share job a frame of which it has been asked to give back.
        self._sends = BackgroundTasks()
        self._unacked: dict[int, int] = {}
        self._preempting: dict[int, str] = {}
        # /metrics carries no process CPU otherwise: one Python loop admits,
        # dispatches, takes every result and answers every ``status``.
        self._process_cpu = self.metrics.counter(
            "master_process_cpu_seconds_total",
            "CPU seconds of the master's process (time.process_time()), "
            "brought up to date by every pass of the scheduler loop",
        )
        self._process_cpu_seen = 0.0
        self._note_process_cpu()
        rendered_twice_counter(self.metrics).inc(0.0, cause="none")
        # What a lost worker moves, at 0 from the service's start: a scrape
        # tells "none yet" from "no such counter".
        evictions_counter(self.metrics).inc(0.0)
        silent_seconds_counter(self.metrics).inc(0.0)
        cut_writes_counter(self.metrics).inc(0.0)
        self._handed_back = self.metrics.counter(
            "sched_units_handed_back_total",
            "Units that left the worker that held them without a result, by "
            "cause (master/state.py::HANDBACK_CAUSES)",
            labels=("cause",),
        )
        for cause in HANDBACK_CAUSES:
            self._handed_back.inc(0.0, cause=cause)
        self._blocked_on_silent_seconds = self.metrics.counter(
            "sched_job_blocked_on_silent_worker_seconds_total",
            "Job-seconds of running jobs with nothing pending and every unit "
            "in flight with a silent worker: they wait for its reconnect or "
            "its eviction, and hold no active slot meanwhile",
        )
        self._blocked_on_silent_seconds.inc(0.0)
        self._barrier_unmet = self.metrics.gauge(
            "sched_admission_barrier_unmet_job_units",
            "Queued jobs whose wait_for_number_of_workers exceeds the live pool",
        )
        self._barrier_unmet.set(0)
        # job_ids of running jobs blocked on a silent worker, as of this pass
        self._blocked: set[str] = set()

    # -- ClusterManager hooks -------------------------------------------------

    def _state_for_job(self, job_name: str | None) -> ClusterManagerState | None:
        if job_name is None:
            return None
        run = self._active_by_name.get(job_name)
        return run.state if run is not None else None

    def _active_states(self) -> list[ClusterManagerState]:
        return [
            run.state
            for run in (self._runs[job_id] for job_id in self._running)
            if run.state is not None
        ]

    def _count_handback(self, cause: str) -> None:
        self._handed_back.inc(cause=cause)

    def _job_for_name(self, job_name: str | None):
        """Resolve an ACTIVE job for the cost model (scene key + tile
        grid); a defunct job's late observations price as the default
        scene — still useful worker-speed signal."""
        if job_name is None:
            return None
        run = self._active_by_name.get(job_name)
        return run.spec.job if run is not None else None

    def _active_job_announcements(self):
        return [
            (run.state.trace_id, run.job_id, run.spec.job)
            for run in (self._runs[job_id] for job_id in self._running)
            if run.state is not None
        ]

    def _jobs_view(self) -> dict:
        """Every queued and running job and the newest ``ENDED_JOBS_LISTED``
        ended ones, in submit order. An ended job's view is the one frozen as
        it ended, so the list costs its live jobs' views and one dict
        lookup an ended job; ``job_status(job_id)`` still answers for every
        job the service has had."""
        listed = self._ended + self._running + self._admission
        return {job_id: self._runs[job_id].view() for job_id in sorted(listed, key=self._submit_order)}

    @staticmethod
    def _submit_order(job_id: str) -> int:
        return int(job_id.rpartition("-")[2])

    def _note_process_cpu(self) -> None:
        used = time.process_time()
        self._process_cpu.inc(max(0.0, used - self._process_cpu_seen))
        self._process_cpu_seen = used

    async def _announce_active_jobs(self, worker: WorkerHandle) -> None:
        """A late joiner is replayed one announcement per RUNNING job, and
        joins each such job's ``job_announce`` span if that is still open."""
        for _trace_id, job_id, _job in self._active_job_announcements():
            await self._announce(self._runs[job_id], worker)

    def _announce_beside(self, run: JobRun, workers: list[WorkerHandle]) -> None:
        """Each worker's announcement on a task of its own, beside the
        loop: one whose socket is slow to take it, or is lost under it,
        holds back nobody (each prepares the job and reports it ready on
        its own). A worker's sender keeps its messages in order, so the
        announcement still precedes any frame of the job."""
        for worker in workers:
            self._sends.spawn(
                self._announce(run, worker),
                name=f"announce-{run.job_id}-{worker.worker_id:08x}",
            )

    def _on_worker_reconnected(self, worker: WorkerHandle) -> None:
        """Back inside its window with its id and its queue: it is told of
        the jobs admitted while it was silent."""
        super()._on_worker_reconnected(worker)
        for job_id in self._running:
            run = self._runs[job_id]
            if run.state is not None and worker.worker_id not in run.announce_sent:
                self._announce_beside(run, [worker])

    async def _announce(self, run: JobRun, worker: WorkerHandle) -> None:
        assert run.state is not None
        run.announce_sent.add(worker.worker_id)
        if worker.prepares_jobs and not run.announce_closed:
            run.announced_at[worker.worker_id] = time.time()
        try:
            await worker.send_job_started(
                trace_id=run.state.trace_id, job_id=run.job_id, job=run.spec.job
            )
        except Exception as e:  # noqa: BLE001 - its silence will evict it
            run.announce_sent.discard(worker.worker_id)
            run.announced_at.pop(worker.worker_id, None)
            logger.warning(
                "job-started announce to %08x failed: %s", worker.worker_id, e
            )

    def _announce_histogram(self):
        return self.metrics.histogram(
            "sched_job_announce_seconds",
            "From a job's admission to the first (edge=first_ready) and to "
            "the last (edge=all_ready) of the workers it was announced to "
            "reporting it ready",
            labels=("edge",),
        )

    def _on_worker_job_ready(
        self, worker: WorkerHandle, job_name: str, job_id: str | None
    ) -> None:
        run = self._active_by_name.get(job_name)
        if (
            run is None
            or run.job_id != job_id
            or run.admitted_at is None
            or worker.worker_id not in run.announced_at
            or worker.worker_id in run.ready_at
        ):
            return
        now = time.time()
        sent = run.announced_at[worker.worker_id]
        run.ready_at[worker.worker_id] = now
        self.span_tracer.complete(
            "announce worker",
            cat="sched",
            start_wall=sent,
            duration=max(0.0, now - sent),
            track=f"job {run.job_id}",
            args={"job_id": run.job_id, "worker": f"{worker.worker_id:08x}"},
        )
        if len(run.ready_at) == 1:
            self._announce_histogram().observe(
                max(0.0, now - run.admitted_at), edge="first_ready"
            )
        self._close_announce_if_all_ready(run, now)

    def _close_announce_if_all_ready(self, run: JobRun, now: float) -> None:
        """Write the job's ``job_announce`` span once every live worker it
        was announced to has reported it ready (a worker that died in
        between is no longer waited for)."""
        if run.announce_closed or not run.ready_at:
            return
        live = {w.worker_id for w in self.live_workers()}
        if any(
            worker_id in live and worker_id not in run.ready_at
            for worker_id in run.announced_at
        ):
            return
        self._announce_histogram().observe(
            max(0.0, now - run.admitted_at), edge="all_ready"
        )
        self._write_announce_span(run, now, all_ready=True)

    def _write_announce_span(self, run: JobRun, now: float, *, all_ready: bool) -> None:
        run.announce_closed = True
        self.span_tracer.complete(
            "job_announce",
            cat="sched",
            start_wall=run.admitted_at,
            duration=max(0.0, now - run.admitted_at),
            track=f"job {run.job_id}",
            args={
                "job_id": run.job_id,
                "job_name": run.job_name,
                "announced": len(run.announced_at),
                "ready": len(run.ready_at),
                "all_ready": all_ready,
            },
        )

    def handbacks_view(self, since: float | None = None) -> list[dict[str, Any]]:
        """Every unit that left a worker without a result, oldest first:
        job, frame (and tile), the worker it left, the cause
        (``master/state.py::HANDBACK_CAUSES``) and when; with ``since``
        only those after that time. Over the jobs ``status`` lists: a job's
        are dropped when it leaves the list of ended jobs, so a client that
        wants them all asks as it goes, with the newest ``at`` it has. A
        unit two workers rendered has an entry here, or the master handed
        it out twice without knowing why."""
        out = []
        for job_id in [*self._ended, *self._running]:
            run = self._runs[job_id]
            if run.state is None:
                continue
            for unit, worker_id, cause, at in run.state.handbacks:
                if since is not None and at <= since:
                    continue
                entry = {
                    "job_id": run.job_id,
                    "job_name": run.job_name,
                    "frame": unit.frame_index,
                    "worker": None if worker_id is None else f"{worker_id:08x}",
                    "cause": cause,
                    "at": at,
                }
                if unit.tile is not None:
                    entry["tile"] = unit.tile
                out.append(entry)
        out.sort(key=lambda entry: entry["at"])
        return out

    def results_view(self, job_id: str) -> dict[str, Any] | None:
        """Whose result finished each finished unit of a job the service has
        had: ``{"job_name", "results": [{"frame", "worker"(, "tile")}]}``. A
        worker that died leaves no record of its own; this is the master's
        of what it delivered. None for an unknown job."""
        run = self._runs.get(job_id)
        if run is None:
            return None
        results = []
        for unit, record in (run.state.frames.items() if run.state is not None else ()):
            if record.status is not FrameStatus.FINISHED:
                continue
            entry: dict[str, Any] = {
                "frame": unit.frame_index,
                "worker": None if record.finished_by is None else f"{record.finished_by:08x}",
            }
            if unit.tile is not None:
                entry["tile"] = unit.tile
            results.append(entry)
        return {"job_name": run.job_name, "results": results}

    # -- lifecycle API --------------------------------------------------------

    def submit(self, spec: JobSpec) -> str:
        """Queue one submission; returns its job_id. Raises on duplicate
        active job names (the wire protocol routes results by job_name,
        so two live jobs must never share one) and when draining."""
        if self._draining:
            raise RuntimeError("Scheduler is draining; not accepting jobs.")
        name = spec.job.job_name
        if name in self._active_by_name or any(
            self._runs[job_id].job_name == name for job_id in self._admission
        ):
            raise ValueError(
                f"A job named {name!r} is already queued or running; "
                "job names must be unique among active jobs."
            )
        self._job_seq += 1
        job_id = f"job-{self._job_seq:04d}"
        run = JobRun(job_id=job_id, spec=spec, submitted_at=time.time())
        self._runs[job_id] = run
        self._admission.append(job_id)
        self.metrics.counter(
            "sched_jobs_submitted_total", "Jobs submitted to the scheduler"
        ).inc()
        self.span_tracer.instant(
            "job submitted",
            cat="sched",
            track=f"job {job_id}",
            args={"job_id": job_id, "job_name": name, "weight": spec.weight,
                  "priority": spec.priority},
        )
        logger.info(
            "Job %s submitted: %r (weight=%g, priority=%d, %d frames).",
            job_id, name, spec.weight, spec.priority, spec.job.frame_count(),
        )
        return job_id

    def job_status(self, job_id: str) -> dict[str, Any] | None:
        run = self._runs.get(job_id)
        return run.view() if run is not None else None

    def scheduler_view(self) -> dict[str, Any]:
        """The ``sched`` section of the metrics snapshot / control status."""
        return {
            "draining": self._draining,
            "admission_queue": list(self._admission),
            "running": list(self._running),
            "total_slots": self._total_slots(),
            "rebalance": self.rebalance_view(),
            "workers": self._workers_view(),
            "jobs": self._jobs_view(),
        }

    def _workers_view(self) -> dict[str, Any]:
        """Each worker the service has had, by id: ``state`` (``live``,
        ``silent`` with ``silent_for_s`` and the seconds its reconnect
        window has left, ``dead`` or ``drained`` with ``ended_at``) and the
        units its queue holds."""
        now = time.time()
        out = {}
        for worker in self.workers.values():
            entry: dict[str, Any] = {"state": worker.state_name, "units": len(worker.queue)}
            if worker.is_dead:
                entry["ended_at"] = worker.ended_at
            if worker.is_silent:
                entry["silent_for_s"] = max(0.0, now - (worker.silent_since or now))
                entry["window_left_s"] = worker.connection.reconnect_window_left()
            out[f"{worker.worker_id:08x}"] = entry
        return out

    def rebalance_view(self) -> dict[str, Any]:
        """This shard's load summary, as the router's rebalancer consumes
        it (sched/rebalance.py): backlog in units, the cost model's
        predicted in-flight seconds (None until the model has history —
        commensurable with ``_share_inputs``'s fallback), and live
        workers. Queued-but-unadmitted jobs count their whole frame
        table; they are backlog this shard owns just as much as pending
        units of running jobs."""
        queue_depth = 0
        in_flight_cost: float | None = None
        for job_id in self._running:
            run = self._runs[job_id]
            assert run.state is not None
            queue_depth += run.state.pending_count() + run.state.in_flight_count()
            cost = self._in_flight_cost(run)
            if cost is not None:
                in_flight_cost = (in_flight_cost or 0.0) + cost
        for job_id in self._admission:
            queue_depth += self._runs[job_id].spec.job.frame_count()
        return {
            "queue_depth": queue_depth,
            "in_flight_cost_seconds": in_flight_cost,
            "workers": len(self.live_workers()),
        }

    async def migrate_workers(
        self, count: int, host: str, port: int, *, reason: str | None = None
    ) -> int:
        """Shed up to ``count`` live workers toward another shard master
        (the router's rebalance move, and its drain-a-dead-shard's-load
        primitive). Workers with the least queued work go first — their
        goodbye returns the fewest frames to this shard's pool — and each
        departs via the graceful migrate-goodbye path, so nothing is lost
        mid-move. Returns how many migrate events were actually sent."""
        workers = sorted(
            self.live_workers(), key=lambda w: len(w.queue.all_frames())
        )
        moved = 0
        for worker in workers[: max(0, int(count))]:
            try:
                await worker.send_migrate(host, port, reason=reason)
            except Exception as e:  # noqa: BLE001 - worker failure mid-send
                logger.warning(
                    "Migrate of worker %08x to %s:%d failed: %s",
                    worker.worker_id, host, port, e,
                )
                continue
            moved += 1
            self.metrics.counter(
                "master_worker_migrate_requests_total",
                "Migrate events sent to workers (shard rebalancing)",
            ).inc()
        return moved

    def cluster_view(self) -> dict:
        view = super().cluster_view()
        view["sched"] = self.scheduler_view()
        return view

    def timeline_other_data(self) -> dict | None:
        """Map the Perfetto ``job job-NNNN`` tracks back to submissions."""
        return {
            "sched_jobs": {
                job_id: {
                    "job_name": run.job_name,
                    "weight": run.spec.weight,
                    "priority": run.spec.priority,
                    "status": run.status,
                    "makespan_seconds": run.makespan_seconds(),
                    "preemptions": run.preemptions,
                }
                for job_id, run in self._runs.items()
            }
        }

    async def cancel_job(self, job_id: str) -> bool:
        """Cancel a queued or running job.

        A running job's not-yet-rendering frames are unqueued from every
        worker (the steal RPC's removal half), frames mid-render finish on
        the worker but their results resolve to a defunct job and are
        accounted as stale, and the job's name is released — the pool's
        slots go back to the remaining jobs with no ghost assignments.
        """
        run = self._runs.get(job_id)
        if (
            run is None
            or run.status in (JOB_FINISHED, JOB_CANCELLED)
            or job_id in self._cancelling
        ):
            return False
        if run.status == JOB_QUEUED:
            self._admission.remove(job_id)
            self._finish_run(run, JOB_CANCELLED, time.time())
            return True
        self._cancelling.add(job_id)
        try:
            # RUNNING: let the job's in-flight assembly stitches land
            # BEFORE its name is released — a same-name resubmit must
            # not race the old stitcher (reading a mixed tile set,
            # unlinking the new job's tile files) on the shared output
            # path. The await window is re-entry-safe via _cancelling.
            await self.assembly.drain_job(run.job_name)
            now = time.time()
            # Deactivate so in-flight events/dispatches resolve to
            # "defunct job" instead of mutating the frozen frame table.
            self._running.remove(job_id)
            self._wfq.remove(job_id)
            self._active_by_name.pop(run.job_name, None)
            self._finish_run(run, JOB_CANCELLED, now)
            # A silent worker is asked nothing: what it holds of the job
            # resolves to a defunct job when it is back, or goes with it.
            for worker in self.serving_workers():
                for frame in worker.queue.frames_for_job(run.job_name):
                    if frame.is_rendering:
                        continue  # its finished event will sweep the mirror
                    try:
                        await worker.unqueue_frame(run.job_name, frame.unit)
                    except Exception as e:  # noqa: BLE001 - worker failure mid-RPC
                        logger.warning(
                            "Cancel of %s: unqueue of unit %s on %08x failed: %s",
                            job_id, frame.unit.label, worker.worker_id, e,
                        )
            return True
        finally:
            self._cancelling.discard(job_id)

    def request_drain(self) -> None:
        """Stop admitting NEW submissions; serve() returns once every
        already-accepted job has finished (or been cancelled)."""
        self._draining = True

    # -- service loop ---------------------------------------------------------

    async def serve(self) -> list[tuple[str, WorkerTrace]]:
        """Bind, run the scheduler until drained, collect worker traces."""
        await self._bind_server()
        try:
            try:
                await self._scheduler_loop()
            finally:
                # Queue-adds still under way belong to jobs that are gone
                # (a drained loop has none running): nothing waits for them.
                for task in self._sends.pending():
                    task.cancel()
                await self._sends.drain()
                # Tiled jobs: stitches scheduled by the last finished
                # events may still be in flight when the loop drains —
                # or when it RAISES; either way they must land, not be
                # destroyed pending at teardown.
                await self.assembly.drain()
            with self.span_tracer.span(
                "collect traces", cat="master", track="job"
            ):
                worker_traces = await self._collect_worker_traces()
            return worker_traces
        finally:
            await self._shutdown_server()

    async def _scheduler_loop(self) -> None:
        """Admission, finalization and one dispatch pass, over and over.

        Between passes the loop waits for the manager's dispatch wake-up
        (master/wakeup.py: a result left a worker nothing queued behind
        the frame it has in hand, a worker reported a job ready, a worker
        connected) or for the tick, whichever comes first. An event-woken
        pass is the ordinary pass: the same picks, the same claim before
        the RPC, and ``dt`` taken from the clock, so share accounting does
        not care how long the wait was."""
        last = time.time()
        while not self.cancellation.is_cancelled():
            now = time.time()
            dt, last = now - last, now
            pass_started = time.perf_counter()
            self._note_process_cpu()
            self._note_blocked_jobs(dt)
            await self._admit_ready_jobs(now)
            self._finalize_finished_jobs(now)
            # SLO tick inline (the single-job master runs a sidecar task
            # instead): window-slide recoveries and deadline breaches
            # surface even for jobs whose result stream has stalled.
            self.slo.tick(now)
            # A job whose unit exhausted its error budget (deterministic
            # render failure — worker_handle sets failed_reason) must not
            # spin redispatch forever: cancel it, releasing the pool.
            for job_id in list(self._running):
                run = self._runs[job_id]
                if run.state is not None and run.state.failed_reason:
                    logger.error(
                        "Job %s failed: %s — cancelling.",
                        job_id,
                        run.state.failed_reason,
                    )
                    # Flight-recorder seam: dump the window leading up to
                    # the failure before the cancel sweeps its state.
                    from tpu_render_cluster.obs.flightrec import (
                        TRIGGER_JOB_FAILURE,
                    )

                    self.flightrec.trigger(
                        TRIGGER_JOB_FAILURE,
                        {
                            "job_id": job_id,
                            "job": run.job_name,
                            "reason": run.state.failed_reason,
                        },
                    )
                    await self.cancel_job(job_id)
            if self._draining and not self._running and self._admission:
                # Liveness under drain: a queued job whose worker barrier
                # exceeds the live pool — with nothing running whose
                # completion could change the picture — would park the
                # service forever. Give late-connecting workers a grace
                # window (the harness submits and drains before its
                # workers even finish their handshakes), then cancel the
                # unadmittable leftovers loudly: the operator asked to
                # wind down.
                if self._drain_stuck_since is None:
                    self._drain_stuck_since = now
                elif (
                    now - self._drain_stuck_since
                    >= self.config.drain_barrier_grace_seconds
                ):
                    self._cancel_unadmittable_queued_jobs(now)
            else:
                self._drain_stuck_since = None
            if self._draining and not self._admission and not self._running:
                return
            if self._running:
                self.tickprof.begin_tick(
                    event_woken=self.dispatch_wakeup.trigger == "event"
                )
                # Fold fresh completion observations into the shared cost
                # model first: this tick's WFQ pick and speculation
                # decisions price off the newest evidence.
                with self.tickprof.phase("pricing"):
                    self.cost_service.ingest(
                        self.live_workers(), self._job_for_name
                    )
                with self.tickprof.phase("share_scan"):
                    inputs = self._tick_inputs()
                with self.tickprof.phase("fair_share"):
                    targets = self._compute_targets(inputs)
                    self._account_shares(dt, targets, inputs)
                with self.tickprof.phase("dispatch"):
                    await self._dispatch_tick(inputs)
                if self.config.preemption:
                    with self.tickprof.phase("preempt"):
                        await self._preempt_tick()
                if self.speculation.config.enabled:
                    # Tail hedging per running job AFTER dispatch: an idle
                    # worker only receives a speculative twin when no
                    # pending work exists for it (maybe_launch gates on
                    # the job's own pool; the dispatch pass above already
                    # consumed every globally-runnable frame this tick).
                    with self.tickprof.phase("speculation"):
                        workers = self.serving_workers()
                        for job_id in list(self._running):
                            run = self._runs[job_id]
                            if run.state is not None:
                                await self.speculation.tick(
                                    run.spec.job,
                                    run.state,
                                    workers,
                                    job_id=job_id,
                                )
                self._finalize_finished_jobs(time.time())
                self.tickprof.end_tick()
            # The whole pass, admission and finalization included, whether or
            # not a job runs: a pass that waited on anything is one
            # observation of that length.
            observe_dispatch_phase(
                self.metrics, "pass", time.perf_counter() - pass_started
            )
            await self.dispatch_wakeup.wait(self.config.tick_seconds)

    def _cancel_unadmittable_queued_jobs(self, now: float) -> None:
        live = len(self.live_workers())
        for job_id in list(self._admission):
            run = self._runs[job_id]
            if run.spec.job.wait_for_number_of_workers > live:
                logger.warning(
                    "Drain: cancelling queued job %s (%r) — its worker "
                    "barrier (%d) exceeds the live pool (%d) and nothing "
                    "is running that could change that.",
                    job_id,
                    run.job_name,
                    run.spec.job.wait_for_number_of_workers,
                    live,
                )
                self._admission.remove(job_id)
                self._finish_run(run, JOB_CANCELLED, now)

    # -- admission ------------------------------------------------------------

    def _admission_order(self) -> list[str]:
        """Queued job_ids, highest priority first, submit order within."""
        return sorted(
            self._admission,
            key=lambda job_id: (-self._runs[job_id].spec.priority, job_id),
        )

    def _note_blocked_jobs(self, dt: float) -> None:
        """Which running jobs wait on a silent worker alone: nothing
        pending, and every unit in flight with a worker whose socket is
        lost. Such a job can use no slot of the pool until that worker is
        back or evicted, so it holds no active slot meanwhile
        (``_admit_ready_jobs``); its wait is counted here."""
        silent = {w.worker_id for w in self.live_workers() if w.is_silent}
        blocked: set[str] = set()
        if silent:
            for job_id in self._running:
                state = self._runs[job_id].state
                if state is None or state.pending_count():
                    continue
                holders = set(state.in_flight_units().values())
                if holders and holders <= silent:
                    blocked.add(job_id)
        for job_id in self._blocked | blocked:
            self._runs[job_id].waiting_on = (
                "silent_worker" if job_id in blocked else None
            )
        if blocked and dt > 0.0:
            self._blocked_on_silent_seconds.inc(dt * len(blocked & self._blocked))
        self._blocked = blocked

    async def _admit_ready_jobs(self, now: float) -> None:
        """Admit queued jobs, highest priority first, while an active slot
        is free. A job whose worker barrier exceeds the live pool is passed
        over and SAID to be (``waiting_on`` in its view,
        ``sched_admission_barrier_unmet_job_units``): a pool that lost a worker
        must not park such a job in silence."""
        live = len(self.live_workers())
        unmet = 0
        for job_id in self._admission_order():
            run = self._runs[job_id]
            if run.status != JOB_QUEUED:
                continue  # cancelled under an admission's await (the ledger's)
            if run.spec.job.wait_for_number_of_workers > live:
                unmet += 1
                run.waiting_on = (
                    f"worker_barrier: wants {run.spec.job.wait_for_number_of_workers}, "
                    f"{live} live"
                )
                continue  # its worker barrier is not met
            if len(self._running) - len(self._blocked) >= self.config.max_active_jobs:
                run.waiting_on = "active_slot"
                continue
            run.waiting_on = None
            await self._admit(run, now)
        self._barrier_unmet.set(unmet)

    async def _admit(self, run: JobRun, now: float) -> None:
        self._admission.remove(run.job_id)
        run.state = ClusterManagerState(run.spec.job)
        run.state.sched_job_id = run.job_id
        run.state.on_handback = self._count_handback
        if self.ledger is not None:
            # WAL the admission + restore what a predecessor incarnation
            # already finished of this job (matched by job_name — the wire
            # routes results by it and active names are unique), then
            # journal new transitions.
            from tpu_render_cluster.ha.failover import adopt_ledger

            # Settle queued appends first: the replay this admission reads
            # must include every transition already scheduled (a closed
            # same-name generation still in the appender queue would
            # otherwise be re-admitted as open).
            if self.ledger_appender is not None:
                await self.ledger_appender.drain()
            _replayed, needs_stitch = adopt_ledger(
                run.state,
                self.ledger,
                metrics=self.metrics,
                spec=run.spec.job.to_dict(),
                job_id=run.job_id,
                weight=run.spec.weight,
                priority=run.spec.priority,
                appender=self.ledger_appender,
            )
            for frame_index in needs_stitch:
                self.assembly.schedule(run.state, frame_index)
        run.status = JOB_RUNNING
        run.admitted_at = now
        self._running.append(run.job_id)
        self._active_by_name[run.job_name] = run
        # SLO tracking from admission (the job's clock starts when it can
        # actually run, not while parked in the admission queue).
        self.slo.register_job(run.spec.job, started_at=now)
        self.metrics.counter(
            "sched_jobs_running_total", "Jobs admitted to the running set"
        ).inc()
        self.metrics.histogram(
            "sched_admission_wait_seconds",
            "Submit-to-admission wait per job",
        ).observe(max(0.0, now - run.submitted_at))
        self._observe_job_phase("queued", now - run.submitted_at)
        self.span_tracer.instant(
            "job admitted",
            cat="sched",
            track=f"job {run.job_id}",
            args={"job_id": run.job_id, "job_name": run.job_name,
                  "wait_s": round(now - run.submitted_at, 6)},
        )
        logger.info("Job %s admitted (%r).", run.job_id, run.job_name)
        # To every worker whose socket is up; a silent one is told when it
        # is back (``_on_worker_reconnected``).
        self._announce_beside(run, self.serving_workers())

    # -- completion / cancellation -------------------------------------------

    def _observe_job_phase(self, phase: str, seconds: float) -> None:
        self.metrics.histogram(
            "sched_job_phase_seconds",
            "A job's time in the scheduler's own hands, by phase: queued "
            "(submit to admission), admit_to_first_dispatch (admission to "
            "its first unit handed to a worker: the announcement, the "
            "workers' preparation, a tick), last_result_to_finished (its "
            "last result taken to the job reported finished)",
            labels=("phase",),
        ).observe(max(0.0, seconds), phase=phase)

    def _finish_run(self, run: JobRun, status: str, now: float) -> None:
        run.status = status
        run.finished_at = now
        for worker in self.workers.values():
            worker.ready_jobs.discard((run.job_name, run.job_id))
        state = run.state
        if run.announced_at and not run.announce_closed:
            # ended before every worker had reported it ready
            self._write_announce_span(run, now, all_ready=False)
        if status == JOB_FINISHED and state is not None:
            self.metrics.histogram(
                "sched_job_worker_units",
                "Distinct workers that rendered a finished job's frames",
                buckets=JOB_WORKERS_BUCKETS,
            ).observe(len({
                record.worker_id for record in state.frames.values()
                if record.worker_id is not None
            }))
        if status == JOB_FINISHED and state is not None and run.admitted_at is not None:
            if state.first_queued_at is not None:
                self._observe_job_phase(
                    "admit_to_first_dispatch", state.first_queued_at - run.admitted_at
                )
            if state.last_finished_at is not None:
                self._observe_job_phase(
                    "last_result_to_finished", now - state.last_finished_at
                )
        if self.ledger_appender is not None and run.state is not None:
            # Close the job's ledger lifecycle so a restarted service does
            # not re-admit it (and a later same-name submission starts a
            # fresh generation). Never-admitted cancels (state None) were
            # never journaled, so there is nothing to close. Scheduled
            # through the FIFO appender: ordered after the job's queued
            # unit appends, fsync'd off the scheduler loop.
            if status == JOB_FINISHED:
                self.ledger_appender.schedule(
                    self.ledger.append_job_finished, run.job_name
                )
            else:
                self.ledger_appender.schedule(
                    self.ledger.append_job_cancelled, run.job_name
                )
        # Final SLO verdict (deadline judged at the true end; no-op for
        # jobs without objectives or never admitted).
        self.slo.finish_job(run.job_name)
        counter = (
            "sched_jobs_finished_total"
            if status == JOB_FINISHED
            else "sched_jobs_cancelled_total"
        )
        help_text = (
            "Jobs that completed every frame"
            if status == JOB_FINISHED
            else "Jobs cancelled before completion"
        )
        self.metrics.counter(counter, help_text).inc()
        self.metrics.gauge(
            "sched_job_share",
            "Instantaneous in-flight share per job",
            labels=("job",),
        ).set(0.0, job=run.job_id)
        if run.admitted_at is not None:
            self.span_tracer.complete(
                "job",
                cat="sched",
                start_wall=run.admitted_at,
                duration=max(0.0, now - run.admitted_at),
                track=f"job {run.job_id}",
                args={
                    "job_id": run.job_id,
                    "job_name": run.job_name,
                    "status": status,
                    "weight": run.spec.weight,
                    "priority": run.spec.priority,
                    "preemptions": run.preemptions,
                },
            )
        else:
            self.span_tracer.instant(
                "job cancelled before admission",
                cat="sched",
                track=f"job {run.job_id}",
                args={"job_id": run.job_id, "job_name": run.job_name},
            )
        logger.info("Job %s %s (%r).", run.job_id, status, run.job_name)
        run.final_view = run.view()
        self._ended.append(run.job_id)
        while len(self._ended) > ENDED_JOBS_LISTED:
            # no longer listed: what the service reported of its units goes
            unlisted = self._runs[self._ended.pop(0)].state
            if unlisted is not None:
                unlisted.handbacks.clear()

    def _finalize_finished_jobs(self, now: float) -> None:
        for job_id in list(self._running):
            run = self._runs[job_id]
            if (
                run.state is not None
                and run.state.all_frames_finished()
                and (
                    self.assembly.has_pending(run.job_name)
                    or run.state.speculations
                )
            ):
                # A tiled job's last stitches are still writing — or a
                # speculation race is unresolved (the winner just landed;
                # the next speculation tick must unqueue the loser and
                # account the outcome): stay RUNNING (and keep the name
                # reserved) until both settle — a status poll must never
                # say "finished" before the frame files exist, and a
                # same-name resubmit must not race the old stitcher on
                # the same output path. The next tick finalizes.
                continue
            if run.state is not None and run.state.all_frames_finished():
                # Ghost copies of units an accepted late result finished:
                # nothing will render them now that the job is done, so
                # sweep their mirror entries (and close their flows)
                # before the job's name is released.
                state = run.state
                job_name = run.job_name
                for worker in self.live_workers():
                    worker.sweep_finished_units(
                        lambda name: state if name == job_name else None
                    )
                self._running.remove(job_id)
                self._wfq.remove(job_id)
                self._active_by_name.pop(run.job_name, None)
                self._finish_run(run, JOB_FINISHED, now)

    # -- fair-share dispatch --------------------------------------------------

    def _total_slots(self) -> int:
        return self.config.target_queue_size * len(self.live_workers())

    def _in_flight_cost(self, run: JobRun) -> float | None:
        """The job's in-flight work in predicted seconds, or None before
        the cost model has any worker history (all jobs fall back to unit
        counts together — the inputs stay commensurable)."""
        if not self.cost_service.model.has_history():
            return None
        assert run.state is not None
        total = 0.0
        for unit, record in run.state.frames.items():
            if record.status not in (
                FrameStatus.QUEUED_ON_WORKER,
                FrameStatus.RENDERING_ON_WORKER,
            ) or record.worker_id is None:
                continue
            total += self.cost_service.predict_unit_seconds(
                record.worker_id, unit, run.spec.job
            )
        return total

    def _share_inputs(
        self, include_cost: bool | None = None
    ) -> list[fair_share.JobShareInput]:
        """Full rescan of every running job's share inputs (the legacy
        ``scan`` tick path, and the oracle ``verify`` mode checks the
        heap against). ``include_cost=False`` pins load metering to unit
        counts — verify mode does this on BOTH sides, because heap-vs-
        scan equivalence is exact there while cost predictions refresh
        on different schedules (per tick vs per dirty job)."""
        if include_cost is None:
            include_cost = self.config.tick_mode != "verify"
        out = []
        for job_id in self._running:
            run = self._runs[job_id]
            assert run.state is not None
            out.append(
                fair_share.JobShareInput(
                    job_id=job_id,
                    weight=run.spec.weight,
                    priority=run.spec.priority,
                    in_flight=run.state.in_flight_count(),
                    pending=self._dispatchable_pending(run),
                    in_flight_cost=(
                        self._in_flight_cost(run) if include_cost else None
                    ),
                )
            )
        return out

    def _dispatchable_pending(self, run: JobRun) -> int:
        """The job's pending units, or 0 while no live worker could take
        one: every worker that prepares announced jobs is still preparing
        this one. Such a job asks for no slot, so nothing is preempted for
        it; a worker's ready event bumps the state's version and the next
        tick resyncs."""
        assert run.state is not None
        if any(
            worker.is_ready_for(run.job_name, run.job_id)
            for worker in self.serving_workers()
        ):
            return run.state.pending_count()
        return 0

    def _pick_for_worker(self, worker: WorkerHandle, job_id: str, inputs_fn) -> str | None:
        """``job_id`` (the fair pick) if ``worker`` has reported it ready,
        else the fair pick among the jobs it has (``inputs_fn()``: this
        moment's share inputs): a worker still preparing one job goes on
        with the others'."""
        if worker.is_ready_for(self._runs[job_id].job_name, job_id):
            return job_id
        return fair_share.pick_job_to_dispatch([
            job for job in inputs_fn()
            if worker.is_ready_for(self._runs[job.job_id].job_name, job.job_id)
        ])

    # -- incremental WFQ (heap/verify tick modes) -----------------------------

    def _cost_metered(self) -> bool:
        return (
            self.config.tick_mode != "verify"
            and self.cost_service.model.has_history()
        )

    def _sync_wfq(self) -> None:
        """Resync the WFQ entries of jobs whose state CHANGED since their
        last sync (the dirty set — state.version covers every transition,
        including evictions and steals that only move a unit between
        workers), drop departed jobs, and admit new ones. Pricing a dirty
        job walks its in-flight units (bounded by the pool's slots), not
        its whole frame table."""
        running = set(self._running)
        for job_id in self._wfq.job_ids():
            if job_id not in running:
                self._wfq.remove(job_id)
        cost_on = self._cost_metered()
        for job_id in self._running:
            run = self._runs[job_id]
            state = run.state
            assert state is not None
            if not self._wfq.needs_sync(job_id, state.version, cost_on):
                continue
            cost = None
            if cost_on:
                cost = 0.0
                for unit, worker_id in state.in_flight_units().items():
                    cost += self.cost_service.predict_unit_seconds(
                        worker_id, unit, run.spec.job
                    )
            self._wfq.sync(
                job_id,
                weight=run.spec.weight,
                priority=run.spec.priority,
                in_flight=state.in_flight_count(),
                pending=self._dispatchable_pending(run),
                cost=cost,
                state_version=state.version,
            )

    def _tick_inputs(self) -> list[fair_share.JobShareInput]:
        """This tick's share inputs: a full rescan in ``scan`` mode, a
        dirty-jobs-only resync + O(jobs) entry read otherwise."""
        if self.config.tick_mode == "scan":
            return self._share_inputs()
        self._sync_wfq()
        return self._wfq.inputs()

    def _verify_pick(
        self,
        heap_pick: str | None,
        scan_inputs: list[fair_share.JobShareInput],
    ) -> None:
        """``verify`` tick mode: assert the heap's dispatch pick matches
        the legacy scan's over the same mid-tick information (the local
        dispatch counters — both sides see dispatches they made, neither
        sees events that landed during awaits). Picks inside the scan's
        ``_EPS`` tie tolerance (same priority, keys within 1e-9) may
        legitimately resolve either way; anything wider is a sync bug."""
        scan_pick = fair_share.pick_job_to_dispatch(scan_inputs)
        if heap_pick == scan_pick:
            return
        by_id = {job.job_id: job for job in scan_inputs}
        a = by_id.get(heap_pick) if heap_pick is not None else None
        b = by_id.get(scan_pick) if scan_pick is not None else None
        if (
            a is not None
            and b is not None
            and a.priority == b.priority
            and abs(a.load / a.weight - b.load / b.weight) <= 1e-9
        ):
            return
        raise AssertionError(
            f"WFQ heap/scan dispatch pick divergence: heap={heap_pick!r} "
            f"(key={self._wfq.key_of(heap_pick) if heap_pick else None}) "
            f"scan={scan_pick!r} over {scan_inputs!r}"
        )

    def _verify_preemption(
        self, wfq_inputs: list[fair_share.JobShareInput]
    ) -> None:
        """``verify`` tick mode: the preemption decision derived from the
        synced entries must equal the one from a fresh state rescan (both
        count-metered, so equality is exact)."""
        scan_inputs = self._share_inputs(include_cost=False)
        total = self._total_slots()
        scan_decision = fair_share.pick_preemption(
            scan_inputs, fair_share.compute_slot_targets(scan_inputs, total)
        )
        wfq_decision = fair_share.pick_preemption(
            wfq_inputs, fair_share.compute_slot_targets(wfq_inputs, total)
        )
        if scan_decision != wfq_decision:
            raise AssertionError(
                f"WFQ heap/scan preemption divergence: heap={wfq_decision!r} "
                f"scan={scan_decision!r} over {scan_inputs!r}"
            )

    def _compute_targets(
        self, inputs: list[fair_share.JobShareInput] | None = None
    ) -> dict[str, float]:
        # ``inputs`` lets the tick loop compute _share_inputs (an
        # O(frames)-per-job scan for the predicted in-flight cost) ONCE
        # and reuse it across targets/accounting/dispatch.
        if inputs is None:
            inputs = self._share_inputs()
        return fair_share.compute_slot_targets(inputs, self._total_slots())

    def _account_shares(
        self,
        dt: float,
        targets: dict[str, float],
        inputs: list[fair_share.JobShareInput] | None = None,
    ) -> None:
        """Fold one tick into the share gauges + overlap-window integrals."""
        if dt <= 0.0:
            return
        if inputs is None:
            inputs = self._share_inputs()
        total_slots = self._total_slots()
        total_in_flight = sum(job.in_flight for job in inputs)
        overlapping = len(inputs) >= 2
        share_gauge = self.metrics.gauge(
            "sched_job_share",
            "Instantaneous in-flight share per job",
            labels=("job",),
        )
        target_gauge = self.metrics.gauge(
            "sched_job_target_share",
            "Fair-share target share per job",
            labels=("job",),
        )
        for job in inputs:
            run = self._runs[job.job_id]
            target_share = (
                targets.get(job.job_id, 0.0) / total_slots if total_slots else 0.0
            )
            achieved_share = (
                job.in_flight / total_in_flight if total_in_flight else 0.0
            )
            run.last_target_share = target_share
            share_gauge.set(achieved_share, job=job.job_id)
            target_gauge.set(target_share, job=job.job_id)
            if overlapping:
                run.overlap_in_flight_integral += job.in_flight * dt
                run.overlap_total_integral += total_in_flight * dt
                run.overlap_target_integral += target_share * dt
                run.overlap_seconds += dt

    async def _dispatch_tick(
        self, inputs: list[fair_share.JobShareInput] | None = None
    ) -> None:
        """Fill every under-target worker with the fairest job's frames:
        the picks one after another here, the queue-add RPCs beside the
        pass, a task a worker (``_send_claims``). The tick profiler's
        ``dispatch`` phase is therefore the picking and claiming alone;
        what a queue-add's round trip costs stays in
        ``master_assignment_latency_seconds`` and ``dispatch_rpc_await``.

        ``heap`` mode picks each slot's job with an O(log n) heap peek
        and folds the dispatch into the entry; ``scan`` keeps the legacy
        per-slot O(jobs) input rebuild over local counters; ``verify``
        runs both and asserts every pick agrees (dispatch decisions
        follow the scan so a tolerated near-tie divergence cannot
        compound).
        """
        mode = self.config.tick_mode
        use_heap = mode in ("heap", "verify")
        track_counts = mode in ("scan", "verify")
        # Local counters adjusted as dispatches land, so one tick's fills
        # interleave jobs fairly instead of recounting O(frames) per slot.
        # The third element is the job's predicted in-flight seconds
        # (None before cost-model history): the WFQ pick meters load by
        # it, and each dispatch folds its unit's prediction in so one
        # tick's fills stay cost-fair too.
        counts: dict[str, list] = {}
        if track_counts:
            for job in inputs if inputs is not None else self._share_inputs():
                counts[job.job_id] = [
                    job.in_flight, job.pending, job.in_flight_cost
                ]

        def inputs_now() -> list[fair_share.JobShareInput]:
            out = []
            for job_id in self._running:
                if job_id not in counts:
                    continue
                run = self._runs[job_id]
                in_flight, pending, in_flight_cost = counts[job_id]
                out.append(
                    fair_share.JobShareInput(
                        job_id=job_id,
                        weight=run.spec.weight,
                        priority=run.spec.priority,
                        in_flight=in_flight,
                        pending=pending,
                        in_flight_cost=in_flight_cost,
                    )
                )
            return out

        # Plan here, send beside. Every pick and every claim of a unit is
        # made in this pass without an await, in the order they were always
        # made (the emptiest worker first, each filled to the target); each
        # worker's queue-adds then go out on a task of their own, in order,
        # and the pass does not wait for them. A worker slow to acknowledge
        # holds back its own frames and nobody else's: its unacknowledged
        # claims count against its target, so the next pass (a result
        # elsewhere starts one at once) fills the others and leaves it be.
        def depth(worker: WorkerHandle) -> int:
            return len(worker.queue) + self._unacked.get(worker.worker_id, 0)

        workers = sorted(self.serving_workers(), key=depth)
        # The kind of this pass goes with its claims: by the time a
        # queue-add is acknowledged the loop may be in its next pass.
        trigger = self.dispatch_wakeup.trigger
        anything_pending = True
        for worker in workers:
            claims: list[tuple[JobRun, Any]] = []
            while (
                anything_pending
                and not worker.is_dead
                and depth(worker) + len(claims) < self.config.target_queue_size
            ):
                if mode == "heap":
                    job_id, inputs_fn = self._wfq.pick_dispatch(), self._wfq.inputs
                else:
                    if mode == "verify":
                        self._verify_pick(self._wfq.pick_dispatch(), inputs_now())
                    job_id = fair_share.pick_job_to_dispatch(inputs_now())
                    inputs_fn = inputs_now
                if job_id is None:
                    anything_pending = False  # nothing pending anywhere
                    break
                job_id = self._pick_for_worker(worker, job_id, inputs_fn)
                if job_id is None:
                    break  # nothing pending that this worker has reported ready
                run = self._runs[job_id]
                assert run.state is not None
                unit = claim_pending_unit(worker, run.state)
                if unit is None:
                    # The pending pool emptied under the entry: stop
                    # offering it this pass; the next sync restores it.
                    if track_counts:
                        counts[job_id][1] = max(0, counts[job_id][1] - 1)
                    if use_heap:
                        self._wfq.on_dispatch_failed(job_id)
                    break
                # The claimed unit's predicted seconds go into the local
                # cost ledger with the claim, so one pass's fills stay
                # cost-fair too.
                predicted = self.cost_service.predict_unit_seconds(
                    worker.worker_id, unit, run.spec.job
                )
                if track_counts:
                    counts[job_id][0] += 1
                    counts[job_id][1] -= 1
                    if counts[job_id][2] is not None:
                        counts[job_id][2] += predicted
                if use_heap:
                    self._wfq.on_dispatched(job_id, predicted)
                claims.append((run, unit))
            if claims:
                self._unacked[worker.worker_id] = (
                    self._unacked.get(worker.worker_id, 0) + len(claims)
                )
                self._sends.spawn(
                    self._send_claims(worker, claims, trigger),
                    name=f"queue-adds-{worker.worker_id:08x}",
                )

    async def _send_claims(
        self, worker: WorkerHandle, claims: list[tuple[JobRun, Any]], trigger: str
    ) -> None:
        """One worker's share of a dispatch pass, in order, on a task of
        its own. After a queue-add that fails (the worker died mid-RPC, a
        cancel raced) the rest of its claims go back to their pools
        unsent; every such return bumps the job's state version, so the
        next pass (the tick's: a worker that refuses, as a draining one
        does, must not be asked again at once) resyncs its entry from the
        truth."""
        failed = False
        for run, unit in claims:
            assert run.state is not None
            try:
                if failed:
                    if claim_is_to_return(worker, run.state, unit):
                        run.state.return_frame_to_pending(unit, "dispatch_failed")
                    continue
                failed = not await send_claimed_unit(
                    worker, run.spec.job, run.state, unit, job_id=run.job_id,
                    trigger=trigger,
                )
            finally:
                left = self._unacked.get(worker.worker_id, 0) - 1
                if left > 0:
                    self._unacked[worker.worker_id] = left
                else:
                    self._unacked.pop(worker.worker_id, None)

    async def _preempt_tick(self) -> None:
        """Pick this pass's preemptions; each one's unqueue RPC goes out on
        a task beside the loop (``_preempt``), like the queue-adds: a
        victim slow to answer, or dead and not yet known to be, holds back
        its own frame and nobody's pass."""
        # 0 legitimately disables per-tick preemption without touching
        # TRC_SCHED_PREEMPTION.
        for _ in range(max(0, self.config.max_preemptions_per_tick)):
            # Recomputed per iteration on purpose (dispatch and any prior
            # preemption changed the in-flight picture) — but ONCE per
            # iteration, shared by targets and the preemption pick. The
            # heap path's recompute is a dirty-jobs resync + O(jobs)
            # entry read (the transitions dispatch just made making those
            # jobs dirty), no frame scans.
            inputs = self._tick_inputs()
            if self.config.tick_mode == "verify":
                self._verify_preemption(inputs)
            targets = self._compute_targets(inputs)
            decision = fair_share.pick_preemption(inputs, targets)
            if decision is None:
                return
            over_id, starved_id = decision
            if over_id in self._preempting.values():
                return  # one of its frames is on its way back: look again after the answer
            run = self._runs[over_id]
            assert run.state is not None
            found = self._find_preemptible_frame(run.job_name, self._runs[starved_id])
            if found is None:
                return  # everything the job holds is already rendering
            victim, frame = found
            self._preempting[victim.worker_id] = over_id
            self._sends.spawn(
                self._preempt(run, starved_id, victim, frame),
                name=f"preempt-{victim.worker_id:08x}",
            )

    async def _preempt(
        self, run: JobRun, starved_id: str, victim: WorkerHandle, frame: Any
    ) -> None:
        assert run.state is not None
        try:
            if not await preempt_frame(run.spec.job, run.state, victim, frame.unit):
                return
        finally:
            self._preempting.pop(victim.worker_id, None)
        run.preemptions += 1
        self.metrics.counter(
            "sched_preemptions_total",
            "Frames unqueued from over-share jobs back to their pool",
            labels=("job",),
        ).inc(job=run.job_id)
        self.span_tracer.instant(
            "preempt",
            cat="sched",
            track=f"job {run.job_id}",
            args={
                "job_id": run.job_id,
                "for_job": starved_id,
                "frame": frame.frame_index,
                "worker": f"{victim.worker_id:08x}",
            },
        )

    def _find_preemptible_frame(
        self, job_name: str, starved: JobRun
    ) -> tuple[WorkerHandle, Any] | None:
        """The job's NEWEST not-yet-rendering mirrored frame (preempting
        the most recently queued wastes the least accumulated wait and is
        the frame least likely to be picked up mid-RPC), on a worker that
        could take a frame of the ``starved`` job in its place."""
        best: tuple[WorkerHandle, Any] | None = None
        for worker in self.serving_workers():
            if worker.worker_id in self._preempting:
                continue  # asked already, and has not answered
            if not worker.is_ready_for(starved.job_name, starved.job_id):
                continue
            for frame in worker.queue.frames_for_job(job_name):
                if frame.is_rendering:
                    continue
                if best is None or frame.queued_at > best[1].queued_at:
                    best = (worker, frame)
        return best

