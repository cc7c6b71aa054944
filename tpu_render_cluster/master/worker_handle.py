"""Per-worker façade on the master.

Aggregates the logical (reconnectable) connection, sender, router, queue
mirror, heartbeat task, and incoming-event handling — the asyncio
re-expression of the reference's ``Worker`` struct
(master/src/connection/mod.rs:36-423). Public surface:
``queue_frame`` / ``unqueue_frame`` (RPC + mirror/state sync),
``finish_job_and_get_trace`` (600 s timeout RPC —
master/src/connection/requester.rs:97), and ``maintain_heartbeat``
(10 s ping interval — master/src/connection/mod.rs:36-37).

Improvements over the reference (SURVEY.md §7 "known bugs to fix"):
an errored finished-event returns the frame to the pending pool instead of
hanging the job, and a heartbeat failure triggers worker eviction via the
``on_dead`` callback instead of leaving frames assigned to a ghost.

Exactly-once accounting under faults (driven by the chaos engine): every
incoming rendering/finished event is checked against the frame's CURRENT
assignment. A duplicated delivery, a late result from an evicted worker
whose frame was re-rendered elsewhere, or an errored result for a frame
this worker no longer owns are all recorded
(``master_duplicate_results_total`` / ``master_late_results_total`` /
``master_stale_results_total``) instead of corrupting the frame table —
the ledger invariant ``ok_results - duplicates == frames_total`` is what
``chaos/invariants.py`` asserts after every fault run. Master→worker RPCs
additionally carry send-side + ack deadlines so one wedged socket can
never stall the assignment loop for every other worker.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Awaitable, Callable

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.master.queue_mirror import FrameOnWorker, WorkerQueueMirror
from tpu_render_cluster.master.state import ClusterManagerState, FrameStatus
from tpu_render_cluster.master.wakeup import SHALLOW_QUEUE, DispatchWakeup
from tpu_render_cluster.obs import ClockOffsetEstimator, MetricsRegistry, Tracer
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.protocol.frames import DispatchFrameCache, frames_cached
from tpu_render_cluster.transport.actors import (
    DEFAULT_WAIT_TIMEOUT,
    MessageRouter,
    SenderHandle,
    request_response,
)
from tpu_render_cluster.transport.reconnect import ReconnectableServerConnection
from tpu_render_cluster.transport.wirecost import WireAccounting
from tpu_render_cluster.utils.env import env_float, env_int
from tpu_render_cluster.utils.logging import WorkerLogger

HEARTBEAT_INTERVAL_SECONDS = 10.0  # reference: master/src/connection/mod.rs:36
HEARTBEAT_RESPONSE_TIMEOUT = 60.0  # reference: master/src/connection/receiver.rs:27
JOB_FINISH_TRACE_TIMEOUT = 600.0  # reference: master/src/connection/requester.rs:97


def send_deadline_seconds() -> float:
    """Write-side deadline on master→worker sends (``TRC_SEND_DEADLINE_SECONDS``).

    Must exceed ``ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT``
    (30 s) or ordinary reconnect windows would be misread as wedges."""
    return env_float("TRC_SEND_DEADLINE_SECONDS", 45.0)


def rpc_deadline_seconds() -> float:
    """Ack deadline on queue add/remove RPCs (``TRC_RPC_DEADLINE_SECONDS``)."""
    return env_float("TRC_RPC_DEADLINE_SECONDS", DEFAULT_WAIT_TIMEOUT)


def unit_error_limit() -> int:
    """Errored results per unit before the job fails
    (``TRC_MAX_UNIT_ERRORS``). Transient render errors requeue and
    succeed elsewhere well inside this budget; a unit that keeps
    erroring deterministically (e.g. a tiled unit on a backend that
    cannot render sub-frame regions, cluster-wide) must fail the job
    loudly instead of redispatching in a hot loop forever."""
    return env_int("TRC_MAX_UNIT_ERRORS", 8)


def heartbeat_pong_retries() -> int:
    """Extra pings after a missed pong before eviction
    (``TRC_HEARTBEAT_PONG_RETRIES``). A pong can be lost to a transient
    partition that heals within the response window; one retry
    distinguishes that from a dead worker. Send *failures* still evict
    immediately — they mean the socket is gone and the reconnect window
    already expired."""
    return env_int("TRC_HEARTBEAT_PONG_RETRIES", 1)


def rendered_twice_counter(metrics: MetricsRegistry):
    """``sched_units_rendered_twice_total{cause}``: fed where a duplicate
    ok result is taken; the scheduler service exposes it at 0 from its
    start, so a scrape tells "none yet" from "no such counter"."""
    return metrics.counter(
        "sched_units_rendered_twice_total",
        "Ok results for units that already had one, by the cause the master "
        "recorded when the unit left a worker without a result (none: it "
        "recorded nothing)",
        labels=("cause",),
    )


def silent_seconds_counter(metrics: MetricsRegistry):
    """``master_worker_silent_seconds_total``: fed when a silence ends (a
    reconnect or the eviction); the scheduler service exposes it at 0
    from its start."""
    return metrics.counter(
        "master_worker_silent_seconds_total",
        "Seconds workers spent with their socket lost, from the loss to the "
        "reconnect or to the eviction at the reconnect window's end",
    )


def evictions_counter(metrics: MetricsRegistry):
    return metrics.counter(
        "master_worker_evictions_total", "Workers marked dead and evicted"
    )


class WorkerHandle:
    """One connected worker, as seen by the master.

    Its states: LIVE (``not is_dead``, socket up), SILENT (``is_silent``:
    the socket is lost and the reconnect window runs; it keeps its queue
    and is handed nothing new), DEAD (``is_dead``: evicted, its units are
    back with their pools) and DRAINED (``is_dead and drained``: it said
    goodbye and returned its units itself)."""

    # Class-level defaults so partially-constructed handles (tests build
    # them attribute-by-attribute) behave like epoch-less production ones.
    epoch: int | None = None
    connection: ReconnectableServerConnection | None = None
    ended_at: float | None = None
    _shutdown_started = False
    _on_protocol_event = None
    # The worker's handshake said it answers announced jobs with
    # event_job-ready; ``ready_jobs`` holds the (job_name, job_id) pairs it
    # has reported. A worker that did not say so is ready for every job.
    prepares_jobs = False
    ready_jobs: frozenset | set = frozenset()
    # The manager's dispatch wake-up (master/wakeup.py); None on a bare handle.
    _wakeup: DispatchWakeup | None = None
    _on_job_ready = None

    def __init__(
        self,
        worker_id: int,
        connection: ReconnectableServerConnection,
        state: ClusterManagerState | None,
        *,
        on_dead: Callable[["WorkerHandle", str], Awaitable[None]] | None = None,
        metrics: MetricsRegistry | None = None,
        span_tracer: Tracer | None = None,
        dispatch_delay_fn: Callable[[int], float] | None = None,
        state_resolver: Callable[[str | None], ClusterManagerState | None]
        | None = None,
        on_frame_complete: Callable[[ClusterManagerState, int], None]
        | None = None,
        on_unit_latency: Callable[[ClusterManagerState, WorkUnit, float], None]
        | None = None,
        on_protocol_event: Callable[[str, dict], None] | None = None,
        epoch: int | None = None,
        prepares_jobs: bool = False,
        wakeup: DispatchWakeup | None = None,
        on_job_ready: Callable[["WorkerHandle", str, str | None], None]
        | None = None,
    ) -> None:
        self.worker_id = worker_id
        self.prepares_jobs = prepares_jobs
        self._wakeup = wakeup
        # Fires with each job this worker reports ready (the scheduler
        # service closes the job's announcement span on it).
        self._on_job_ready = on_job_ready
        self.ready_jobs = set()
        self.connection = connection
        # Master incarnation epoch (ha/ledger.py; None without a ledger):
        # stamped on every queue-add and checked against the epoch echoed
        # by incoming frame events — an event fenced to a PREVIOUS
        # incarnation is counted and refused, never applied.
        self.epoch = epoch
        # Single-job masters pass the one state; the multi-job scheduler
        # passes ``state=None`` plus a resolver mapping the ``job_name``
        # every worker event carries to the owning job's state (None for
        # a job that is no longer active — cancelled or finished — whose
        # late events are then accounted as stale instead of applied).
        self.state = state
        self._state_resolver = state_resolver
        self.queue = WorkerQueueMirror()
        self.frames_stolen_count = 0
        self.is_dead = False
        # Wall time it was declared dead (evicted) or said goodbye.
        self.ended_at: float | None = None
        # True when is_dead was reached via the graceful goodbye path
        # (counted as a drain, not an eviction).
        self.drained = False
        # Set by shutdown(): failures observed past this point are our
        # own teardown, not worker death (no eviction accounting).
        self._shutdown_started = False
        # Chaos shim: seconds to stall before dispatching a given frame's
        # queue-add RPC (no-op when None — the production default).
        self._dispatch_delay_fn = dispatch_delay_fn
        self.metrics = metrics
        self.span_tracer = span_tracer
        # Wire-cost accounting around the codec (transport/wirecost.py):
        # per-tag byte counters + serialize-time histograms on this end
        # of the socket (passthrough when no registry is wired).
        self._wire = WireAccounting(metrics)
        # Preserialized queue-add codec (protocol/frames.py): the job
        # segment is encoded once per (job generation, epoch) and spliced
        # into each dispatch frame.
        self._frames = DispatchFrameCache()
        # Most recent compact metrics payload this worker piggybacked on a
        # heartbeat pong (None until the first instrumented pong arrives).
        self.latest_worker_metrics: dict | None = None
        # NTP-style clock-offset estimate (worker clock - master clock),
        # fed by the heartbeat's four timestamps; the merged cluster
        # timeline rebases this worker's span events by it.
        self.clock_offset = ClockOffsetEstimator()
        # Chrome trace events the worker piggybacked on its job-finished
        # response ({"process_name", "events"}), for the cluster timeline.
        self.collected_span_events: dict | None = None
        # Fires when an ok result completes a whole FRAME (every tile
        # landed): the master's assembly hook. Sync by contract — the
        # implementation schedules its own task so event handling never
        # blocks on image stitching.
        self._on_frame_complete = on_frame_complete
        # Fires with each unit's winning-result dispatch-to-result latency
        # (the master_unit_latency_seconds stream) — the SLO engine's feed.
        self._on_unit_latency = on_unit_latency
        # Flight-recorder digest feed (obs/flightrec.py): compact
        # protocol-event summaries (dispatches, accepted results, fence
        # refusals, death) — cheap enough for the hottest event paths.
        self._on_protocol_event = on_protocol_event
        # Observed per-unit render durations (for scheduler cost models),
        # keyed (job_name, unit) — frame indices alias across jobs.
        self._rendering_started_at: dict[tuple[str, WorkUnit], float] = {}
        self._completion_observations: list[tuple[str, WorkUnit, float]] = []
        self._on_dead = on_dead
        self.logger = WorkerLogger(
            logging.getLogger("master.worker"),
            pm.worker_id_to_string(worker_id),
            connection.last_known_address,
        )

        self.sender = SenderHandle(self._send_message)
        self.router = MessageRouter(self._receive_message)
        self._heartbeat_task: asyncio.Task | None = None
        self._events_task: asyncio.Task | None = None
        self._silence_task: asyncio.Task | None = None
        self._tasks_started = False

    # -- transport adapters -------------------------------------------------

    async def _send_message(self, message: pm.Message) -> None:
        serialize_started = time.perf_counter()
        if (
            isinstance(message, pm.MasterFrameQueueAddRequest)
            and frames_cached()
        ):
            # Preserialized dispatch path: the job segment comes from the
            # per-generation cache and only the varying keys are spliced;
            # the wire accounting observes the already-encoded text (one
            # serialize per message end-to-end, never a re-encode to
            # measure). Byte-identical to encode_message by contract.
            text = self._frames.encode(message)
            self._wire.record_send(
                message.type_name,
                text,
                time.perf_counter() - serialize_started,
            )
        else:
            text = self._wire.encode(message)
        if isinstance(message, pm.MasterFrameQueueAddRequest):
            # The per-dispatch JSON cost ROADMAP item 3 wanted
            # preserialized, attributed as a tick phase (both paths, so
            # the A/B reads off one metric). Import is lazy:
            # sched/__init__ imports the manager which imports this
            # module, so a top-level sched import here would be circular.
            from tpu_render_cluster.sched.tickprof import observe_dispatch_phase

            observe_dispatch_phase(
                self.metrics,
                "dispatch_serialize",
                time.perf_counter() - serialize_started,
            )
        # Send-side deadline: a socket that accepts writes but never
        # drains (or a reconnect window that never closes) must surface as
        # a failure here instead of parking the sender actor — and with it
        # every RPC on this worker — forever.
        await asyncio.wait_for(
            self.connection.send_text(text),
            send_deadline_seconds(),
        )

    async def _receive_message(self) -> pm.Message:
        return self._wire.decode(await self.connection.receive_text())

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        """Spawn sender/receiver/heartbeat/event tasks."""
        assert not self._tasks_started
        self._tasks_started = True
        self.sender.start()
        self.router.start()
        self._events_task = asyncio.create_task(
            self._manage_incoming_events(), name=f"events-{self.worker_id:08x}"
        )
        self._heartbeat_task = asyncio.create_task(
            self._maintain_heartbeat(), name=f"heartbeat-{self.worker_id:08x}"
        )
        self._silence_task = asyncio.create_task(
            self._watch_silence(), name=f"silence-{self.worker_id:08x}"
        )

    def cancel_heartbeat(self) -> None:
        if self._heartbeat_task is not None:
            self._heartbeat_task.cancel()

    async def shutdown(self) -> None:
        # An in-flight heartbeat send racing this teardown fails with
        # "sender closed" — that is US closing, not the worker dying, and
        # must not count an eviction (or requeue frames) on the way out.
        self._shutdown_started = True
        self.cancel_heartbeat()
        for task in (self._events_task, self._silence_task):
            if task is not None:
                task.cancel()
        await self.router.stop()
        await self.sender.stop()
        self.connection.close()

    async def _mark_dead(self, reason: str) -> None:
        if self.is_dead or self._shutdown_started:
            return
        self.is_dead = True
        self.ended_at = time.time()
        self.logger.warning("Worker marked dead: %s", reason)
        if self._on_protocol_event is not None:
            self._on_protocol_event(
                "worker_dead",
                {"worker": self._worker_label(), "reason": reason},
            )
        # Terminate the Perfetto flows of every assignment still mirrored
        # here: the requeued frames open fresh chains elsewhere, and a
        # dangling flow-start would fail the trace validator on artifacts
        # from any run that lost a worker.
        now = time.time()
        for frame in self.queue.all_frames():
            self._complete_frame_flow(
                "frame evicted",
                frame.unit,
                frame.trace,
                start_wall=now,
                duration=0.0,
                extra_args={"reason": reason},
            )
        if self.metrics is not None:
            evictions_counter(self.metrics).inc()
            # Zero (don't leave stale) this worker's depth: its frames are
            # returned to pending and re-queue elsewhere, and a frozen
            # nonzero series would double-count them in the live view.
            self.metrics.gauge(
                "master_worker_queue_depth",
                "Frames currently mirrored on each worker's queue",
                labels=("worker",),
            ).set(0, worker=self._worker_label())
        if self._on_dead is not None:
            await self._on_dead(self, reason)

    @property
    def is_silent(self) -> bool:
        """Live with its socket lost: it may reconnect for what is left of
        the window, keeps its queue meanwhile, and is handed nothing new."""
        return (
            not self.is_dead
            and self.connection is not None
            and not self.connection.is_connected
        )

    @property
    def silent_since(self) -> float | None:
        """Wall time of the socket's loss while ``is_silent``, else None."""
        return self.connection.silent_since if self.is_silent else None

    @property
    def state_name(self) -> str:
        """``live``, ``silent``, ``dead`` or ``drained``: what ``status``
        and the cluster view say of this worker."""
        if self.is_dead:
            return "drained" if self.drained else "dead"
        return "silent" if self.is_silent else "live"

    async def _watch_silence(self) -> None:
        """From the socket's loss to the reconnect, or to the eviction at
        the reconnect window's end: the one place that declares a worker
        dead for having stayed away (an operation that runs into the
        window's end only fails). One ``worker silent`` span a silence on
        the worker's track of the master's timeline."""
        while True:
            await self.connection.wait_silent()
            if self.is_dead or self._shutdown_started:
                return
            since = self.connection.silent_since or time.time()
            units_held = len(self.queue)
            # (a worker that leaves with its job is silent too, holding nothing)
            self.logger.log(
                logging.WARNING if units_held else logging.INFO,
                "Worker's socket is lost; %d unit(s) stay with it for %.0f s.",
                units_held,
                self.connection.reconnect_window_left(),
            )
            back = await self.connection.wait_connected()
            if self.is_dead or self._shutdown_started:
                return
            seconds = max(0.0, time.time() - since)
            if self.metrics is not None:
                silent_seconds_counter(self.metrics).inc(seconds)
            if self.span_tracer is not None:
                self.span_tracer.complete(
                    "worker silent",
                    cat="master",
                    start_wall=since,
                    duration=seconds,
                    track=f"worker-{self._worker_label()}",
                    args={
                        "worker": self._worker_label(),
                        "units_held": units_held,
                        "ended": "reconnected" if back else "evicted",
                    },
                )
            if not back:
                await self._mark_dead(
                    "did not reconnect within the wait window"
                )
                return

    # -- state routing --------------------------------------------------------

    def _state_for(self, job_name: str | None) -> ClusterManagerState | None:
        """The frame table owning ``job_name``'s frames (see __init__)."""
        if self._state_resolver is not None:
            return self._state_resolver(job_name)
        return self.state

    @staticmethod
    def _job_generation_mismatch(
        state: ClusterManagerState | None, event_job_id: str | None
    ) -> bool:
        """True when an event is stamped with a DIFFERENT submission's
        job_id than the active job of the same name — i.e. the name was
        reused after a cancel/finish and this event belongs to the old
        generation. Anonymous events (C++ workers echo no job_id) always
        match."""
        return (
            state is not None
            and event_job_id is not None
            and state.sched_job_id is not None
            and event_job_id != state.sched_job_id
        )

    # -- observability helpers ----------------------------------------------

    def _worker_label(self) -> str:
        return pm.worker_id_to_string(self.worker_id)

    def _update_queue_depth_gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge(
                "master_worker_queue_depth",
                "Frames currently mirrored on each worker's queue",
                labels=("worker",),
            ).set(len(self.queue), worker=self._worker_label())

    def _complete_frame_flow(
        self,
        name: str,
        unit: WorkUnit,
        trace: pm.TraceContext | None,
        *,
        start_wall: float,
        duration: float,
        extra_args: dict | None = None,
    ) -> None:
        """Master-side terminal span for one assignment chain (result
        received / frame stolen), with the flow arrowhead bound inside it
        when the assignment's trace context is known."""
        if self.span_tracer is None:
            return
        args = {"frame": unit.frame_index, **(extra_args or {})}
        if unit.tile is not None:
            args["tile"] = unit.tile
        track = f"worker-{self._worker_label()}"
        if trace is not None:
            args["flow"] = trace.flow_id
        self.span_tracer.complete(
            name,
            cat="master",
            start_wall=start_wall,
            duration=duration,
            track=track,
            args=args,
        )
        if trace is not None:
            flow_args = {"frame": unit.frame_index}
            if unit.tile is not None:
                flow_args["tile"] = unit.tile
            self.span_tracer.flow_end(
                "frame",
                id=trace.flow_id,
                ts=start_wall + duration / 2.0,
                cat="frame",
                track=track,
                args=flow_args,
            )

    # -- scheduling RPCs ----------------------------------------------------

    async def queue_frame(
        self,
        job: BlenderJob,
        unit: WorkUnit | int,
        *,
        stolen_from: int | None = None,
        job_id: str | None = None,
        speculative: bool = False,
        trigger: str | None = None,
    ) -> None:
        """RPC a work unit onto this worker's queue; sync mirror + state.

        Reference: master/src/connection/mod.rs:139-168. ``job_id`` is the
        multi-job scheduler's submission id, piggybacked on the wire and
        echoed by (Python) workers; single-job dispatch leaves it None.
        ``unit.tile`` rides the same optional-key idiom — whole-frame
        dispatch encodes byte-identically to before (a bare int is
        accepted as a whole-frame unit for legacy callers/tests).

        ``speculative=True`` dispatches a duplicate TWIN of a unit whose
        live assignment stays on its PRIMARY worker: the wire message is
        byte-identical to any other dispatch (workers cannot tell), the
        mirror gains a normal entry here, but the frame record is NOT
        re-pointed — the primary still owns it, so the first accepted ok
        result wins through the existing dedup seam exactly as a
        late-result race would (master/speculate.py resolves the loser).

        ``trigger``: the kind of the dispatch pass that claimed the unit
        (master/wakeup.py), from a caller whose pass does not wait for
        this RPC; None counts the frame to the pass under way.
        """
        if isinstance(unit, int):
            unit = WorkUnit(unit)
        frame_index = unit.frame_index
        if self.is_dead:
            raise RuntimeError("Worker is dead; refusing dispatch.")
        state = self._state_for(job.job_name)
        if state is None:
            # The dispatch raced a cancel: the job is gone, nothing to queue.
            raise RuntimeError(
                f"Job {job.job_name!r} is no longer active; refusing dispatch."
            )
        if self._dispatch_delay_fn is not None:
            delay = self._dispatch_delay_fn(frame_index)
            if delay > 0.0:
                await asyncio.sleep(delay)
        # Fresh span per ASSIGNMENT (not per frame): a re-queued or stolen
        # frame starts a new causal chain with its own Perfetto flow.
        trace = pm.TraceContext.new(state.trace_id)
        request = pm.MasterFrameQueueAddRequest.new(
            job, frame_index, trace=trace, job_id=job_id, tile=unit.tile,
            epoch=self.epoch,
        )
        rpc_started = time.perf_counter()
        rpc_started_wall = time.time()
        response = await request_response(
            self.sender,
            self.router,
            request,
            pm.WorkerFrameQueueAddResponse,
            timeout=rpc_deadline_seconds(),
        )
        if response.result != pm.FRAME_QUEUE_ADD_RESULT_ADDED:
            raise RuntimeError(
                f"Worker rejected frame {frame_index}: {response.error_reason}"
            )
        # The ack can arrive AFTER this worker was evicted (or after the
        # frame finished elsewhere): the eviction already requeued the
        # frame and swept the mirror, so completing the assignment here
        # would stomp the live record and open a Perfetto flow nothing
        # ever closes. The worker may still render its ghost copy; the
        # finished-event dedup path absorbs that result. A job cancelled
        # mid-RPC counts as superseded too — compared by state IDENTITY,
        # so a same-named job resubmitted during the RPC window cannot
        # adopt (and then wedge on) the old submission's dispatch.
        if self._state_for(job.job_name) is not state:
            raise RuntimeError(
                f"Assignment of unit {unit.label} was superseded "
                f"mid-dispatch (job {job.job_name!r} was cancelled/replaced)."
            )
        record = state.frames.get(unit)
        if (
            self.is_dead
            or record is None
            or record.status is FrameStatus.FINISHED
        ):
            raise RuntimeError(
                f"Assignment of unit {unit.label} was superseded "
                f"mid-dispatch ({'worker died' if self.is_dead else 'frame finished or job gone'})."
            )
        rpc_seconds = time.perf_counter() - rpc_started
        if self.metrics is not None:
            strategy = state.job.frame_distribution_strategy.strategy_type
            self.metrics.histogram(
                "master_assignment_latency_seconds",
                "queue-add RPC round-trip (request sent to ack received)",
                labels=("strategy",),
            ).observe(rpc_seconds, strategy=strategy)
            # Attribution phase: dispatch send->ack (lazy import, see
            # _send_message for the sched<->master cycle note).
            from tpu_render_cluster.sched.tickprof import observe_dispatch_phase

            observe_dispatch_phase(self.metrics, "dispatch_rpc_await", rpc_seconds)
        if self._wakeup is not None:
            self._wakeup.count_dispatched_frame(trigger)
        if self.span_tracer is not None:
            # Constant span name (frame index in args) so viewers and the
            # analysis roll-up aggregate all assignments into one stat.
            args = {"frame": frame_index, "flow": trace.flow_id}
            if unit.tile is not None:
                args["tile"] = unit.tile
            if stolen_from is not None:
                args["stolen_from"] = stolen_from
            track = f"worker-{self._worker_label()}"
            self.span_tracer.complete(
                "assign frame",
                cat="master",
                start_wall=rpc_started_wall,
                duration=rpc_seconds,
                track=track,
                args=args,
            )
            # Flow source, mid-span so it binds inside the assign slice;
            # the worker's queue_wait/read/render/write spans route it and
            # the result-received span terminates it.
            flow_args = {"frame": frame_index}
            if unit.tile is not None:
                flow_args["tile"] = unit.tile
            self.span_tracer.flow_start(
                "frame",
                id=trace.flow_id,
                ts=rpc_started_wall + rpc_seconds / 2.0,
                cat="frame",
                track=track,
                args=flow_args,
            )
        now = time.time()
        self.queue.add(
            FrameOnWorker(
                frame_index,
                queued_at=now,
                stolen_from=stolen_from,
                trace=trace,
                job_name=job.job_name,
                job_id=job_id,
                tile=unit.tile,
            )
        )
        self._update_queue_depth_gauge()
        if self._on_protocol_event is not None:
            self._on_protocol_event(
                "dispatch",
                {
                    "worker": self._worker_label(),
                    "job": job.job_name,
                    "unit": unit.label,
                    "speculative": speculative,
                    "stolen_from": stolen_from,
                },
            )
        if not speculative:
            state.mark_frame_as_queued(
                unit,
                self.worker_id,
                now,
                stolen_from=stolen_from,
                stolen_at=now if stolen_from is not None else None,
            )

    async def unqueue_frame(self, job_name: str, unit: WorkUnit | int) -> str:
        """RPC-remove a work unit (the steal primitive); returns the result
        enum.

        Tolerates the remove-vs-render races (``already-rendering`` /
        ``already-finished`` — reference: strategies.rs:347-373 leaves those
        to the caller).
        """
        if isinstance(unit, int):
            unit = WorkUnit(unit)
        request = pm.MasterFrameQueueRemoveRequest.new(
            job_name, unit.frame_index, tile=unit.tile
        )
        rpc_started_wall = time.time()
        rpc_started = time.perf_counter()
        response = await request_response(
            self.sender,
            self.router,
            request,
            pm.WorkerFrameQueueRemoveResponse,
            timeout=rpc_deadline_seconds(),
        )
        if response.result == pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED:
            removed = self.queue.remove(unit.frame_index, job_name, unit.tile)
            self._update_queue_depth_gauge()
            # A successful steal ends this assignment's causal chain (the
            # thief's queue_frame opens a fresh one) — terminate the flow
            # here so no dangling flow-start survives a stolen frame.
            if self.span_tracer is not None:
                self._complete_frame_flow(
                    "frame stolen",
                    unit,
                    removed.trace if removed is not None else None,
                    start_wall=rpc_started_wall,
                    duration=time.perf_counter() - rpc_started,
                    extra_args={"result": response.result},
                )
        return response.result

    def has_empty_queue(self) -> bool:
        return len(self.queue) == 0

    def sweep_finished_units(self, state_for) -> int:
        """Drop mirror entries whose unit already FINISHED, closing their
        Perfetto flows. These are ghost copies left by accepted LATE
        results: the evicted original's result finished the unit while
        the re-dispatched twin still sat queued here — if the job ends
        before the twin renders, nothing else would ever pop the entry
        (or terminate its flow), and the mirror would keep offering a
        finished unit to steal passes. Called at job finalization; racing
        events for swept entries are absorbed by the dedup seam as usual.
        """
        removed = 0
        now = time.time()
        for frame in self.queue.all_frames():
            state = state_for(frame.job_name)
            if state is None:
                continue
            record = state.frames.get(frame.unit)
            if record is not None and record.status is FrameStatus.FINISHED:
                self.queue.remove(frame.frame_index, frame.job_name, frame.tile)
                self._complete_frame_flow(
                    "frame superseded",
                    frame.unit,
                    frame.trace,
                    start_wall=now,
                    duration=0.0,
                    extra_args={"reason": "finished elsewhere"},
                )
                removed += 1
        if removed:
            self._update_queue_depth_gauge()
        return removed

    def drain_completion_observations(
        self,
    ) -> list[tuple[str, WorkUnit, float]]:
        """Take (job_name, unit, seconds) samples observed since the last
        call (consumed by the shared CostModelService — exactly once no
        matter which scheduler loop ticks first)."""
        observations, self._completion_observations = self._completion_observations, []
        return observations

    # -- job lifecycle RPCs --------------------------------------------------

    async def send_job_started(
        self,
        *,
        trace_id: int | None = None,
        job_id: str | None = None,
        job: BlenderJob | None = None,
    ) -> None:
        """Announce a job start. Single-job callers pass nothing (the one
        state's trace id is used); the multi-job scheduler passes each
        admitted job's (trace_id, job_id) and the job itself — including
        replays to late joiners, one event per active job — so that a
        worker can prepare the job and report it ready."""
        if trace_id is None and self.state is not None:
            trace_id = self.state.trace_id
        await self.sender.send_message(
            pm.MasterJobStartedEvent(trace_id=trace_id, job_id=job_id, job=job)
        )

    def is_ready_for(self, job_name: str, job_id: str | None) -> bool:
        """Whether frames of the job may be handed to this worker: it has
        reported the job ready, or never said it reports."""
        return not self.prepares_jobs or (job_name, job_id) in self.ready_jobs

    async def send_migrate(
        self, host: str, port: int, *, reason: str | None = None
    ) -> None:
        """Ask this worker to re-home to another shard master: it drains
        gracefully (goodbye reason ``"migrate"``, queued frames returned
        and requeued here) and reconnects there with a fresh announce.
        Fire-and-forget like the drain protocol — a reference worker
        ignores the unknown tag and stays."""
        await self.sender.send_message(
            pm.MasterWorkerMigrateEvent(host=host, port=port, reason=reason)
        )

    async def finish_job_and_get_trace(self):
        """Request the worker's trace; 600 s budget for huge traces."""
        request = pm.MasterJobFinishedRequest.new()
        response = await request_response(
            self.sender,
            self.router,
            request,
            pm.WorkerJobFinishedResponse,
            timeout=JOB_FINISH_TRACE_TIMEOUT,
        )
        # Keep the piggybacked span timeline (None from a C++ worker) for
        # the merged cluster timeline export.
        self.collected_span_events = response.span_events
        return response.trace

    # -- background loops ----------------------------------------------------

    def _count_anomaly(
        self,
        name: str,
        help_text: str,
        *,
        state: ClusterManagerState | None = None,
        ledger_key: str | None = None,
    ) -> None:
        if self.metrics is not None:
            self.metrics.counter(name, help_text).inc()
        if state is not None and ledger_key is not None:
            state.ledger[ledger_key] += 1

    def _refuse_stale_epoch(
        self, event: "pm.WorkerFrameQueueItemRenderingEvent | pm.WorkerFrameQueueItemFinishedEvent", kind: str
    ) -> bool:
        """True when the event is fenced out: it echoes an epoch that is
        not this master incarnation's. The result/render DID happen under
        a predecessor, but this master holds no assignment context for it
        (the worker re-announced fresh and its old session's queue state
        was dropped), so applying it would corrupt the frame table; the
        ledger-replayed finished set plus re-dispatch of the remainder is
        the recovery path. Counted in the metrics AND the owning job's
        in-memory ledger, exactly like the other dedup-seam refusals.
        This runs on the master's hottest path (every worker event), so
        everything beyond the three comparisons — including the log
        label — is built only on the rare refusal."""
        if (
            self.epoch is None
            or event.epoch is None
            or event.epoch == self.epoch
        ):
            return False
        if self.metrics is not None:
            self.metrics.counter(
                "master_stale_epoch_events_total",
                "Worker frame events refused because they echo a previous "
                "master incarnation's epoch",
            ).inc()
        state = self._state_for(event.job_name)
        if state is not None:
            state.ledger["stale_epoch_results"] += 1
        if self._on_protocol_event is not None:
            self._on_protocol_event(
                "stale_epoch_refusal",
                {
                    "worker": self._worker_label(),
                    "job": event.job_name,
                    "unit": WorkUnit(event.frame_index, event.tile).label,
                    "event": kind,
                    "epoch": event.epoch,
                    "current_epoch": self.epoch,
                },
            )
        self.logger.warning(
            "Refused %s event for unit %s with stale epoch %d "
            "(current epoch %d).",
            kind,
            WorkUnit(event.frame_index, event.tile).label,
            event.epoch,
            self.epoch,
        )
        return True

    def _is_current_assignment(self, record) -> bool:
        """Does this worker own the frame's LIVE assignment right now?

        False for events from the past: the worker was evicted (record
        re-pointed by requeue), the frame was stolen, or it already
        finished. Events failing this check are accounted, not applied —
        the exactly-once seam.
        """
        return (
            not self.is_dead
            and record is not None
            and record.status
            in (FrameStatus.QUEUED_ON_WORKER, FrameStatus.RENDERING_ON_WORKER)
            and record.worker_id == self.worker_id
        )

    def _mirror_entry_for_event(
        self, unit: WorkUnit, job_name: str, event_job_id: str | None
    ):
        """The mirror entry an incoming event may touch, or None.

        Generation guard: after a cancel + same-name resubmit, the mirror
        key (job_name, frame_index, tile) can be occupied by the NEW
        submission's dispatch while a late event from the OLD one is
        still in flight — only an entry whose job_id matches (or where
        either side is anonymous) belongs to this event.
        """
        entry = self.queue.get(unit.frame_index, job_name, unit.tile)
        if (
            entry is not None
            and entry.job_id is not None
            and event_job_id is not None
            and entry.job_id != event_job_id
        ):
            return None
        return entry

    def _apply_rendering_event(
        self, event: pm.WorkerFrameQueueItemRenderingEvent
    ) -> None:
        if self._refuse_stale_epoch(event, "rendering"):
            return
        unit = WorkUnit(event.frame_index, event.tile)
        state = self._state_for(event.job_name)
        # Keep the mirror honest even for a defunct job: a unit that
        # started rendering must stop looking like a steal candidate —
        # but never touch a same-keyed entry of a NEWER generation.
        if (
            self._mirror_entry_for_event(unit, event.job_name, event.job_id)
            is not None
        ):
            self.queue.set_rendering(unit.frame_index, event.job_name, unit.tile)
        if self._job_generation_mismatch(state, event.job_id):
            state = None
        record = state.frames.get(unit) if state is not None else None
        speculation = (
            state.speculations.get(unit) if state is not None else None
        )
        if (
            speculation is not None
            and self.worker_id == speculation.twin_worker_id
        ):
            # A speculative twin starting to render is BY DESIGN, not an
            # anomaly: record its render-start clock on this handle (the
            # cost observation measures render time if the twin wins) but
            # leave the frame record pointed at the primary — the dedup
            # seam arbitrates the race by first result, not by state.
            self.logger.debug(
                "Speculative twin of unit %s started rendering.", unit.label
            )
            self._rendering_started_at[(event.job_name, unit)] = time.time()
            return
        if state is None or not self._is_current_assignment(record):
            # E.g. the queue-add ack timed out (frame requeued elsewhere)
            # but the add had landed, and the superseded copy now renders;
            # or the job was cancelled while the frame sat on the worker.
            self._count_anomaly(
                "master_stale_results_total",
                "Worker events ignored because the frame's live assignment "
                "moved on (eviction, steal, requeue, cancel, or already "
                "finished)",
                state=state,
                ledger_key="stale_results",
            )
            self.logger.debug(
                "Stale rendering event for unit %s ignored.", unit.label
            )
            return
        self.logger.debug("Unit %s started rendering.", unit.label)
        self._rendering_started_at[(event.job_name, unit)] = time.time()
        state.mark_frame_as_rendering(unit, self.worker_id)

    def _apply_finished_event(
        self, event: pm.WorkerFrameQueueItemFinishedEvent
    ) -> None:
        # Fencing runs before ANY accounting or mirror mutation: a
        # stale-epoch result must not touch the ok/duplicate counters (the
        # exactly-once equation is per incarnation) and must not close a
        # flow this incarnation never opened.
        if self._refuse_stale_epoch(event, "finished"):
            return
        received_wall = time.time()
        received_mono = time.perf_counter()
        unit = WorkUnit(event.frame_index, event.tile)
        state = self._state_for(event.job_name)
        if self._job_generation_mismatch(state, event.job_id):
            state = None
        record = state.frames.get(unit) if state is not None else None
        # Popped unconditionally — the duplicate/late/stale returns below
        # must not leave a ghost in-flight entry on this handle — EXCEPT
        # when the same-keyed entry belongs to a newer generation of a
        # reused job name: that entry is another submission's live
        # assignment, not this event's.
        frame_on_worker = None
        if (
            self._mirror_entry_for_event(unit, event.job_name, event.job_id)
            is not None
        ):
            frame_on_worker = self.queue.remove(
                unit.frame_index, event.job_name, unit.tile
            )
        started = self._rendering_started_at.pop((event.job_name, unit), None)
        self._update_queue_depth_gauge()
        if self._wakeup is not None and len(self.queue) <= SHALLOW_QUEUE:
            # Nothing is queued behind the frame this worker renders (or
            # takes next): the dispatch loop's next pass starts now, not
            # at its tick. It runs after this handler has returned, so it
            # sees the frame table as this event leaves it.
            self._wakeup.set()
        if self.metrics is not None:
            self.metrics.counter(
                "master_frame_results_total",
                "Frame finished events received from workers, by wire result",
                labels=("result",),
            ).inc(result=event.result)
        if state is None:
            # The job is gone (cancelled, or a stale generation of a
            # reused name): account the event, close the assignment's
            # Perfetto flow IF this handle still held it open (an earlier
            # unqueue/evict already terminated it otherwise), apply
            # nothing. This is how a cancelled job's mid-render frames
            # release their workers with no ghost assignments.
            self._count_anomaly(
                "master_stale_results_total",
                "Worker events ignored because the frame's live assignment "
                "moved on (eviction, steal, requeue, cancel, or already "
                "finished)",
            )
            self._complete_frame_flow(
                "frame result",
                unit,
                frame_on_worker.trace if frame_on_worker is not None else None,
                start_wall=received_wall,
                duration=time.perf_counter() - received_mono,
                extra_args={"result": event.result, "job_gone": True},
            )
            self.logger.debug(
                "Result for unit %s of defunct job %r ignored.",
                unit.label,
                event.job_name,
            )
            return
        finished_already = record is None or record.status is FrameStatus.FINISHED
        current = self._is_current_assignment(record)
        # Terminal span of the assignment's causal chain on the master
        # timeline: the flow arrow from "assign frame" through the
        # worker's phases ends here. Prefer the trace the event echoed
        # (exact even across re-queues); fall back to the mirror's record
        # (a C++ worker echoes nothing). The arrowhead belongs to the
        # event that POPPED the mirror entry: a still-mirrored assignment
        # is a still-open chain (eviction, steals, drains, and sweeps all
        # close the flow exactly when they remove the entry), so a late
        # WINNING result — e.g. a speculative twin beating its straggling
        # primary — terminates its own chain, while a result whose entry
        # was already swept must not double-terminate it.
        trace = event.trace
        if trace is None and frame_on_worker is not None:
            trace = frame_on_worker.trace
        self._complete_frame_flow(
            "frame result",
            unit,
            trace if frame_on_worker is not None else None,
            start_wall=received_wall,
            duration=time.perf_counter() - received_mono,
            extra_args={"result": event.result},
        )
        if event.result == pm.FRAME_QUEUE_ITEM_FINISHED_OK:
            state.ledger["ok_results"] += 1
            if finished_already:
                # The duplicate-result race: a duplicated delivery, or the
                # re-render of an evicted frame lost to the original's late
                # result (or vice versa). ``mark_frame_as_finished``'s
                # idempotence keeps ``_finished_count`` exact; this ledger
                # proves the collision happened.
                self._count_anomaly(
                    "master_duplicate_results_total",
                    "Ok results received for frames that were already finished",
                    state=state,
                    ledger_key="duplicate_results",
                )
                if self.metrics is not None:
                    # Two renders of one unit, by what the master knows of
                    # why the unit left a worker that held it (the newest
                    # of ``state.handbacks``; "none": it knows of nothing).
                    newest = next(
                        (c for u, _w, c, _t in reversed(state.handbacks) if u == unit),
                        "none",
                    )
                    rendered_twice_counter(self.metrics).inc(cause=newest)
                self.logger.warning(
                    "Duplicate result for unit %s ignored.", unit.label
                )
                return
            if not current:
                # Late result from a superseded assignment (this worker was
                # evicted / the frame requeued after a timed-out add RPC):
                # the render DID happen and the output exists — accept it.
                # The currently-assigned copy will account as a duplicate.
                self._count_anomaly(
                    "master_late_results_total",
                    "Ok results accepted from superseded assignments",
                    state=state,
                    ledger_key="late_results",
                )
                self.logger.warning(
                    "Late result for unit %s accepted from a superseded "
                    "assignment.",
                    unit.label,
                )
                # The late result IS the unit's winning (first) result —
                # a speculative twin racing a straggling primary lands
                # here by design — so it carries the latency and cost
                # observation the schedulers learn from.
                self._record_winning_result(
                    state, event.job_name, unit, started, frame_on_worker
                )
                self._finish_unit(state, unit)
                return
            self.logger.debug("Unit %s finished.", unit.label)
            self._record_winning_result(
                state, event.job_name, unit, started, frame_on_worker
            )
            self._finish_unit(state, unit)
        else:
            state.ledger["errored_results"] += 1
            if not current:
                # An errored result for a unit this worker no longer owns
                # must NOT requeue it: the live assignment is
                # authoritative, and a second pending entry would render
                # the unit twice.
                self._count_anomaly(
                    "master_stale_results_total",
                    "Worker events ignored because the frame's live assignment "
                    "moved on (eviction, steal, requeue, cancel, or already "
                    "finished)",
                    state=state,
                    ledger_key="stale_results",
                )
                self.logger.warning(
                    "Stale errored result for unit %s ignored.",
                    unit.label,
                )
                return
            # Reference workers swallow render errors and the master
            # hangs (worker/src/rendering/queue.rs:169-174); we
            # reschedule the unit instead — up to the error budget, past
            # which the failure is evidently deterministic and the job
            # fails rather than livelocking on redispatch.
            record.errored_count += 1
            if record.errored_count >= unit_error_limit():
                state.failed_reason = (
                    f"unit {unit.label} errored {record.errored_count} "
                    f"times (last: {event.error_reason}); giving up"
                )
                self.logger.error("Job failed: %s", state.failed_reason)
                return
            self.logger.warning(
                "Unit %s errored on worker (%s); rescheduling "
                "(attempt %d/%d).",
                unit.label,
                event.error_reason,
                record.errored_count,
                unit_error_limit(),
            )
            state.return_frame_to_pending(unit, "error")

    def _record_winning_result(
        self,
        state: ClusterManagerState,
        job_name: str,
        unit: WorkUnit,
        started: float | None,
        frame_on_worker,
    ) -> None:
        """Account the unit's FIRST accepted ok result: the cost-model
        observation, the exact per-unit latency log, and its histogram.
        Duplicate copies (the speculation loser, a re-delivered send)
        never reach here — they return through the dedup branches.

        Two different clocks on purpose: the COST observation measures
        processing time (render start when the rendering event was seen)
        — what the predictors model — while the LATENCY log measures
        dispatch-to-result (queue-add to result received) — what a unit
        actually waited, the tail the speculation bench is judged on. The
        latency clock starts at the unit's EARLIEST live dispatch, not
        the winning copy's: a hedged unit that waited on a straggler
        before its twin was even launched must carry that wait, or the
        speculation A/B would compare incommensurable clocks."""
        now = time.time()
        queued_at = (
            frame_on_worker.queued_at if frame_on_worker is not None else None
        )
        processing_from = started if started is not None else queued_at
        if processing_from is None:
            return  # mirror already swept and no rendering event seen
        self._completion_observations.append(
            (job_name, unit, max(1e-4, now - processing_from))
        )
        record = state.frames.get(unit)
        dispatch_times = [
            t
            for t in (
                queued_at,
                record.queued_at if record is not None else None,
            )
            if t is not None
        ]
        latency_from = min(dispatch_times) if dispatch_times else processing_from
        latency = max(1e-4, now - latency_from)
        state.unit_seconds.append(latency)
        if self._on_protocol_event is not None:
            self._on_protocol_event(
                "unit_finished",
                {
                    "worker": self._worker_label(),
                    "job": job_name,
                    "unit": unit.label,
                    "latency_seconds": round(latency, 6),
                },
            )
        if self.metrics is not None:
            self.metrics.histogram(
                "master_unit_latency_seconds",
                "Dispatch-to-result latency of each unit's winning "
                "assignment (queue-add to result received)",
            ).observe(latency)
        if self._on_unit_latency is not None:
            self._on_unit_latency(state, unit, latency)

    def _finish_unit(self, state: ClusterManagerState, unit: WorkUnit) -> None:
        """Mark a unit finished; when it completes its whole frame, fire
        the master's frame-complete hook (assembly of tiled frames). The
        transition returns True exactly once per frame, so a duplicate or
        late copy of the final tile can never assemble a frame twice.
        Also stamps a live speculation's winner — the speculation loop
        resolves the loser off this mark."""
        speculation = state.speculations.get(unit)
        if speculation is not None and speculation.winner_worker_id is None:
            speculation.winner_worker_id = self.worker_id
        frame_completed = state.mark_frame_as_finished(unit, by=self.worker_id)
        if (
            frame_completed
            and state.job.tile_grid is not None
            and self._on_frame_complete is not None
        ):
            self._on_frame_complete(state, unit.frame_index)

    async def _handle_goodbye(self, event: pm.WorkerGoodbyeEvent) -> None:
        """Graceful drain: requeue the returned frames without an eviction.

        The goodbye's frame list is advisory — anything still mirrored
        here is swept too — and each frame is requeued only if this worker
        still owns its live assignment, so a goodbye racing an eviction
        (or a steal) can never double-pend a frame.
        """
        if self.is_dead:
            return  # eviction won the race; frames are already requeued
        self.is_dead = True
        self.ended_at = time.time()
        self.drained = True
        self.cancel_heartbeat()
        now = time.time()
        # Mirror entries carry their owning job; the advisory units the
        # goodbye shipped are attributed to its (single) job_name — in a
        # multi-job cluster the mirror sweep is authoritative anyway,
        # since everything the master credits to this worker is mirrored.
        items = {(f.job_name, f.unit) for f in self.queue.all_frames()}
        tiles = event.returned_tiles or (None,) * len(event.returned_frames)
        items |= {
            (event.job_name, WorkUnit(index, tile))
            for index, tile in zip(event.returned_frames, tiles)
        }
        requeued = 0
        for job_name, unit in sorted(
            items, key=lambda item: (item[0] or "", item[1].sort_key)
        ):
            state = self._state_for(job_name)
            record = state.frames.get(unit) if state is not None else None
            frame = self.queue.remove(unit.frame_index, job_name, unit.tile)
            if frame is not None:
                self._complete_frame_flow(
                    "frame returned",
                    unit,
                    frame.trace,
                    start_wall=now,
                    duration=0.0,
                    extra_args={"reason": event.reason},
                )
            if (
                record is not None
                and record.status is not FrameStatus.FINISHED
                and record.worker_id == self.worker_id
            ):
                state.return_frame_to_pending(unit, "drain")
                requeued += 1
        self._update_queue_depth_gauge()
        if self.metrics is not None:
            if event.reason == "migrate":
                # A rebalance re-home is not an operator drain: counted
                # apart so the chaos audits' drain ledger stays exact.
                self.metrics.counter(
                    "master_worker_migrations_total",
                    "Workers that departed via a master-requested migrate "
                    "goodbye (shard rebalancing)",
                ).inc()
            else:
                self.metrics.counter(
                    "master_worker_drains_total",
                    "Workers that departed gracefully via the goodbye message",
                ).inc()
        self.logger.info(
            "Worker drained gracefully (%s); %d frame(s) requeued.",
            event.reason,
            requeued,
        )

    async def _manage_incoming_events(self) -> None:
        """Apply rendering/finished/goodbye events to the mirror + state.

        Reference: master/src/connection/mod.rs:240-326 (the goodbye
        branch is the drain extension).
        """
        rendering_queue = self.router.subscribe(pm.WorkerFrameQueueItemRenderingEvent)
        finished_queue = self.router.subscribe(pm.WorkerFrameQueueItemFinishedEvent)
        goodbye_queue = self.router.subscribe(pm.WorkerGoodbyeEvent)
        ready_queue = self.router.subscribe(pm.WorkerJobReadyEvent)

        async def handle_ready() -> None:
            while True:
                event = await ready_queue.get()
                self.ready_jobs.add((event.job_name, event.job_id))
                if self._on_job_ready is not None:
                    self._on_job_ready(self, event.job_name, event.job_id)
                state = self._state_for(event.job_name)
                if state is not None:
                    # the job's dispatchable demand changed: the scheduler
                    # resyncs it on its next pass, which starts now
                    state.version += 1
                    if self._wakeup is not None:
                        self._wakeup.set()

        async def handle_rendering() -> None:
            while True:
                self._apply_rendering_event(await rendering_queue.get())

        async def handle_finished() -> None:
            while True:
                self._apply_finished_event(await finished_queue.get())

        async def handle_goodbye() -> None:
            while True:
                await self._handle_goodbye(await goodbye_queue.get())

        # gather instead of asyncio.TaskGroup so the master still runs on
        # Python 3.10; first failure cancels the sibling loop the same way.
        tasks = [
            asyncio.ensure_future(handle_rendering()),
            asyncio.ensure_future(handle_finished()),
            asyncio.ensure_future(handle_goodbye()),
            asyncio.ensure_future(handle_ready()),
        ]
        try:
            await asyncio.gather(*tasks)
        except asyncio.CancelledError:
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        except Exception as e:  # noqa: BLE001 - loop death is a worker failure
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            await self._mark_dead(f"event loop failed: {e}")

    async def _maintain_heartbeat(self) -> None:
        """Ping every 10 s; heartbeat failure marks the worker dead.

        Reference: master/src/connection/mod.rs:327-423, except failure
        triggers eviction instead of only killing the heartbeat task, and
        the two failure modes are separated: a SEND failure (socket gone,
        reconnect window expired) evicts immediately, while a missed PONG
        gets ``heartbeat_pong_retries()`` re-pings first — a pong lost to
        a transient partition that healed must not evict a live worker.
        """
        pong_queue = self.router.subscribe(pm.WorkerHeartbeatResponse)
        missed = 0
        try:
            while True:
                # Ping FIRST, then sleep (the reference sleeps first): the
                # immediate first exchange seeds the clock-offset estimator
                # at registration time, so even short jobs get their worker
                # rows rebased in the merged cluster timeline. Safe against
                # drops because the worker subscribes its heartbeat queue
                # before starting its receive loop.
                request = pm.MasterHeartbeatRequest.new_now()
                sent_at = time.perf_counter()
                try:
                    await self.sender.send_message(request)
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 - socket definitively gone
                    await self._mark_dead(f"heartbeat send failed: {e}")
                    return
                try:
                    # The predicate discards stale pongs (answers to an
                    # earlier, timed-out ping): matching one to THIS ping
                    # would feed the clock estimator a sample whose four
                    # timestamps span two exchanges. Anonymous pongs (C++
                    # workers echo nothing) always match — they carry no
                    # clock timestamps, so nothing can be corrupted.
                    pong = await self.router.wait_for_message(
                        pm.WorkerHeartbeatResponse,
                        predicate=lambda p: p.echo_request_time is None
                        or p.echo_request_time == request.request_time,
                        timeout=HEARTBEAT_RESPONSE_TIMEOUT,
                        queue=pong_queue,
                    )
                except asyncio.CancelledError:
                    raise
                except asyncio.TimeoutError:
                    missed += 1
                    if missed > heartbeat_pong_retries():
                        await self._mark_dead(
                            f"no heartbeat response after {missed} pings"
                        )
                        return
                    self.logger.warning(
                        "Heartbeat pong missed (%d); re-pinging.", missed
                    )
                    continue
                except Exception as e:  # noqa: BLE001
                    await self._mark_dead(f"heartbeat failed: {e}")
                    return
                correlated = pong.echo_request_time is not None or missed == 0
                missed = 0
                pong_wall = time.time()
                if self.metrics is not None and correlated:
                    # An ANONYMOUS pong right after a miss may be the
                    # timed-out ping's late answer (C++ workers echo no
                    # request time), so its RTT against THIS ping is
                    # meaningless — skip the observation.
                    self.metrics.histogram(
                        "transport_heartbeat_rtt_seconds",
                        "Heartbeat ping->pong round-trip per worker",
                        labels=("worker",),
                    ).observe(
                        time.perf_counter() - sent_at,
                        worker=self._worker_label(),
                    )
                if pong.received_at is not None and pong.responded_at is not None:
                    self._observe_clock_sample(
                        request.request_time,
                        pong.received_at,
                        pong.responded_at,
                        pong_wall,
                    )
                if pong.metrics is not None:
                    self.latest_worker_metrics = pong.metrics
                await asyncio.sleep(HEARTBEAT_INTERVAL_SECONDS)
        except asyncio.CancelledError:
            raise
        finally:
            self.router.unsubscribe(pm.WorkerHeartbeatResponse, pong_queue)

    def _observe_clock_sample(
        self, t1: float, t2: float, t3: float, t4: float
    ) -> None:
        """Fold one NTP exchange into the estimator and export the gauges."""
        self.clock_offset.add_ping(t1, t2, t3, t4)
        if self.metrics is None:
            return
        label = self._worker_label()
        self.metrics.gauge(
            "master_worker_clock_offset_seconds",
            "Estimated worker-minus-master wall clock offset in SECONDS "
            "(median of the heartbeat NTP window; positive = the worker "
            "clock reads ahead of the master)",
            labels=("worker",),
        ).set(self.clock_offset.offset(), worker=label)
        self.metrics.gauge(
            "master_worker_clock_drift_ppm",
            "Estimated worker clock drift rate vs the master in "
            "parts-per-million (microseconds of divergence per elapsed "
            "second; positive = the worker clock runs fast)",
            labels=("worker",),
        ).set(self.clock_offset.drift_ppm(), worker=label)
