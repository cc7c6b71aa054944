"""Cluster manager: the master's top-level orchestration.

Lifecycle (reference: master/src/cluster/mod.rs:484-672):
bind -> accept connections (3-step app handshake; first-connection builds a
worker, reconnecting swaps the socket into the existing logical connection)
-> barrier-wait for ``wait_for_number_of_workers`` -> broadcast job-started
-> run the distribution strategy to completion -> collect every worker's
trace (cancelling its heartbeat first; 600 s budget) -> shut down.

Improvements over the reference, kept behaviorally compatible:
- late-joining workers receive ``event_job-started`` at handshake time (the
  reference acknowledges this hole at master/src/cluster/mod.rs:616-617);
- a worker that misses heartbeats or fails mid-RPC is *evicted*: its queued
  frames return to the pending pool so the job still finishes (the
  reference leaves them assigned forever — SURVEY.md §5.3).
"""

from __future__ import annotations

import asyncio
import logging
import time

from pathlib import Path

from tpu_render_cluster import PROTOCOL_VERSION
from tpu_render_cluster.ha.ledger import AsyncLedgerAppender
from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.master.assembly import FrameAssemblyService
from tpu_render_cluster.master.speculate import (
    SpeculationService,
    speculation_loop,
)
from tpu_render_cluster.master.state import ClusterManagerState
from tpu_render_cluster.master.strategies import run_strategy
from tpu_render_cluster.master.wakeup import DispatchWakeup
from tpu_render_cluster.master.worker_handle import WorkerHandle
from tpu_render_cluster.obs import (
    FlightRecorder,
    HistorySampler,
    HistoryStore,
    LoopLagMonitor,
    MetricsRegistry,
    SnapshotWriter,
    TimelineProcess,
    Tracer,
    get_registry,
    merge_wire,
    resolve_flight_directory,
    tracer_process,
)
from tpu_render_cluster.obs.flightrec import (
    TRIGGER_EPOCH_FENCE,
    TRIGGER_JOB_FAILURE,
    TRIGGER_MASTER_FAILOVER,
    TRIGGER_SLO_ALERT,
    TRIGGER_WORKER_EVICTION,
)
from tpu_render_cluster.obs.http import TelemetryServer
from tpu_render_cluster.obs.slo import TRANSITION_FIRE, SloService, slo_loop
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.traces.master_trace import MasterTrace
from tpu_render_cluster.traces.worker_trace import WorkerTrace
from tpu_render_cluster.transport.reconnect import (
    ReconnectableServerConnection,
    TransportMetrics,
)
from tpu_render_cluster.transport.wirecost import WireAccounting
from tpu_render_cluster.transport.ws import (
    WebSocketClosed,
    WebSocketConnection,
    websocket_accept,
)
from tpu_render_cluster.utils.cancellation import CancellationToken

logger = logging.getLogger(__name__)

HANDSHAKE_TIMEOUT = 30.0
BARRIER_POLL_SECONDS = 1.0  # reference: master/src/cluster/mod.rs:568-585


def job_state_view(state: ClusterManagerState) -> dict:
    """One job's live work-unit accounting + exactly-once ledger (the
    shared shape of the single-job and scheduler ``jobs`` sections). The
    ``frames_*`` keys count UNITS (tiles under a tile grid) — the quantity
    the dispatch/dedup machinery meters; the ``assembly`` section carries
    the frame-level view for tiled jobs."""
    total = len(state.frames)
    finished = state.finished_count()
    pending = state.pending_count()
    view = {
        "frames_total": total,
        "frames_finished": finished,
        "frames_pending": pending,
        "frames_in_flight": total - finished - pending,
        "ledger": dict(state.ledger),
    }
    if state.job.tile_grid is not None:
        view["assembly"] = state.assembly_view()
    return view


class ClusterManager:
    """Runs one job across a cluster of connected workers."""

    def __init__(
        self,
        host: str,
        port: int,
        job: BlenderJob | None,
        *,
        metrics: MetricsRegistry | None = None,
        span_tracer: Tracer | None = None,
        metrics_snapshot_path: str | Path | None = None,
        dispatch_delay_fn=None,
        output_base_directory: str | Path | None = None,
        telemetry_port: int | None = None,
        ledger=None,
        ledger_resume: bool = False,
        flight_directory: str | Path | None = None,
    ) -> None:
        self.host = host
        self.port = port
        # Write-ahead job ledger (ha/ledger.py; None = the reference
        # single-incarnation behavior, byte-identical wire traffic). When
        # set, the master stamps the ledger's epoch on handshakes and
        # queue-adds, journals every unit-finished/frame-assembled
        # transition, and — on a restart/standby takeover — starts from
        # the replayed finished set instead of re-rendering it.
        self.ledger = ledger
        self.epoch: int | None = ledger.epoch if ledger is not None else None
        # ``job=None`` is the SERVICE mode used by the multi-job scheduler
        # subclass (sched/manager.py JobManager): no frame table exists at
        # construction; per-job states are created at admission and looked
        # up through ``_state_for_job``. The single-job contract (one job,
        # one state, reference wire traffic) is unchanged when a job is
        # given.
        self.job = job
        # Chaos shim: ``(worker_id, frame_index) -> seconds`` to stall a
        # queue-add dispatch (master/worker_handle.py). None in production.
        self._dispatch_delay_fn = dispatch_delay_fn
        self.state = ClusterManagerState(job) if job is not None else None
        self.workers: dict[int, WorkerHandle] = {}
        self.cancellation = CancellationToken()
        # Defaults to the process-global registry so process-scoped sources
        # (ops/assignment's greedy-fallback counter, the render path) land
        # in the same snapshot as the master's own series.
        self.metrics = metrics if metrics is not None else get_registry()
        self.span_tracer = span_tracer or Tracer("master")
        self._transport_metrics = TransportMetrics(self.metrics)
        # What the shallow-queue dispatch loops (naive-fine, the service
        # loop) wait on between passes in place of a whole tick
        # (master/wakeup.py): set by a worker's handle when a result leaves
        # it nothing queued behind the frame in hand, or it reports a job
        # ready, and here when a worker connects.
        self.dispatch_wakeup = DispatchWakeup(self.metrics)
        # Tiled frames: when the last tile of a frame lands, the assembly
        # service stitches the tile files into the frame's final image
        # (master/assembly.py). ``output_base_directory`` resolves a job's
        # %BASE% output prefix on the master's filesystem (None = the
        # job's paths are usable as-is, e.g. the in-process harness).
        self.assembly = FrameAssemblyService(
            metrics=self.metrics,
            span_tracer=self.span_tracer,
            base_directory=output_base_directory,
        )
        # Predictive scheduling (ROADMAP item 3): the shared cost model —
        # warm-started from a ``TRC_COST_MODEL`` snapshot when one is set,
        # refined online from every completion observation — plus the
        # straggler-hedging speculation engine (master/speculate.py; off
        # unless ``TRC_SPECULATION`` enables it). Imported lazily: the
        # sched package's __init__ imports the scheduler, which imports
        # this module.
        from tpu_render_cluster.sched.cost_model import (
            DEFAULT_COST_EMA_ALPHA,
            CostModelService,
            load_cost_model_from_env,
        )

        # A tpu-batch job's configured EMA alpha governs the shared model
        # (a loaded TRC_COST_MODEL snapshot carries its own).
        alpha = DEFAULT_COST_EMA_ALPHA
        if (
            job is not None
            and job.frame_distribution_strategy.strategy_type == "tpu-batch"
            and job.frame_distribution_strategy.tpu_batch is not None
        ):
            alpha = job.frame_distribution_strategy.tpu_batch.cost_ema_alpha
        self.cost_service = CostModelService(
            load_cost_model_from_env(), alpha=alpha, metrics=self.metrics
        )
        self.speculation = SpeculationService(
            cost=self.cost_service,
            metrics=self.metrics,
            span_tracer=self.span_tracer,
        )
        # Continuous observability (obs/history.py + obs/flightrec.py):
        # the embedded metrics-history ring sampled by an in-process loop
        # (started at bind, final sample at shutdown) serves /history and
        # feeds the always-on flight recorder, which dumps a blackbox
        # bundle on SLO fires, evictions, job failures, epoch-fence
        # refusals, and failover adoption.
        self.history = HistoryStore(self.metrics)
        self._history_sampler = HistorySampler(self.history)
        self.flightrec = FlightRecorder(
            history=self.history,
            span_tracer=self.span_tracer,
            metrics=self.metrics,
            directory=resolve_flight_directory(
                flight_directory,
                Path(metrics_snapshot_path).parent
                if metrics_snapshot_path is not None
                else None,
            ),
        )
        # Event-loop lag probe (obs/loopmon.py): started at bind, stopped
        # at shutdown; a sample over TRC_OBS_LOOPMON_THRESHOLD counts a
        # blocked episode and flight-records the window.
        self.loopmon = LoopLagMonitor(
            self.metrics,
            role="master",
            span_tracer=self.span_tracer,
            flightrec=self.flightrec,
        )
        # Handshake-path wire accounting (transport/wirecost.py); the
        # per-worker handles carry their own instance over the same
        # registry, so all master-side series land in one family.
        self._wire = WireAccounting(self.metrics)
        # Per-job SLO engine (obs/slo.py): fed by every winning result's
        # dispatch-to-result latency, ticked by a sidecar (single-job) or
        # the scheduler loop (service mode). Inert for jobs without an
        # [slo] table.
        self.slo = SloService(
            metrics=self.metrics,
            span_tracer=self.span_tracer,
            on_alert=self._on_slo_alert,
        )
        # Pull-based telemetry endpoints (obs/http.py): /metrics (Prom
        # text exposition), /healthz, /clusterz (cluster_view). None =
        # disabled; 0 = ephemeral port (resolved after _bind_server).
        self.telemetry = (
            TelemetryServer(
                self.metrics,
                host=host,
                port=telemetry_port,
                clusterz_fn=self.cluster_view,
                healthz_fn=self._healthz_view,
                history=self.history,
            )
            if telemetry_port is not None
            else None
        )
        # When set, a 1 Hz SnapshotWriter keeps this file fresh while the
        # job runs (live inspection), with a final write at shutdown.
        self._snapshot_writer = (
            SnapshotWriter(
                metrics_snapshot_path,
                self.metrics,
                extra_fn=self.cluster_view,
            )
            if metrics_snapshot_path is not None
            else None
        )
        self._job_started = False
        self._server: asyncio.Server | None = None
        # Frames a previous incarnation finished every tile of but never
        # stitched (crash between last tile and assembly): re-scheduled
        # once the job starts, from the tile files already on disk.
        self._replay_stitch_frames: list[int] = []
        self.replayed_units = 0
        # Durable appends from the event loop go through ONE FIFO appender
        # (ha/ledger.py): the fsync runs on a worker thread, never on the
        # loop serving heartbeats (the loop-blocking lint enforces this).
        self.ledger_appender = (
            AsyncLedgerAppender(self.ledger) if self.ledger is not None else None
        )
        if self.ledger is not None and self.state is not None:
            from tpu_render_cluster.ha.failover import adopt_ledger

            # Open generations always restore (a standby resuming an
            # in-flight job); closed ones only under the explicit
            # ``--resume`` contract — a plain re-run of a completed job
            # starts a fresh generation and renders from scratch.
            self.replayed_units, self._replay_stitch_frames = adopt_ledger(
                self.state,
                self.ledger,
                metrics=self.metrics,
                include_closed=ledger_resume,
                spec=job.to_dict(),
                appender=self.ledger_appender,
            )
            if self.replayed_units or self._replay_stitch_frames:
                # This incarnation adopted a predecessor's in-flight job:
                # record the takeover as a post-mortem bundle (the window
                # is empty this early — the bundle documents the adoption
                # itself: epoch, replayed unit count, pending stitches).
                self.flightrec.trigger(
                    TRIGGER_MASTER_FAILOVER,
                    {
                        "epoch": self.epoch,
                        "replayed_units": self.replayed_units,
                        "replay_stitch_frames": len(self._replay_stitch_frames),
                        "job": job.job_name,
                    },
                )

    # -- multi-job hooks (overridden by sched/manager.py JobManager) --------

    def _state_for_job(self, job_name: str | None) -> ClusterManagerState | None:
        """Map a worker event's ``job_name`` to the owning frame table.

        Single-job masters own exactly one state and every event belongs
        to it; the scheduler subclass resolves against its active-job map
        (returning None for cancelled/finished jobs, whose late events are
        then accounted as stale instead of applied).
        """
        return self.state

    def _active_job_announcements(
        self,
    ) -> list[tuple[int | None, str | None, BlenderJob | None]]:
        """(trace_id, job_id, job) per job a late-joining worker must learn
        of; the job itself only from the scheduler service, whose workers
        prepare a job when it is announced.

        Resolves the inherited reference FIXME (master/src/cluster/mod.rs:
        616-617): a worker whose handshake completes after job start still
        receives the job-started event(s) — generalized to *every* active
        job so it holds with several jobs running concurrently.
        """
        if self._job_started and self.state is not None:
            return [(self.state.trace_id, None, None)]
        return []

    # -- public ------------------------------------------------------------

    async def _bind_server(self) -> None:
        """Bind the accept loop + start the live snapshot writer."""
        self._server = await asyncio.start_server(
            self._on_tcp_connection, self.host, self.port
        )
        actual_port = self._server.sockets[0].getsockname()[1]
        self.port = actual_port
        logger.info("Master listening on %s:%d", self.host, actual_port)
        if self._snapshot_writer is not None:
            self._snapshot_writer.start()
        self._history_sampler.start()
        self.loopmon.start()
        if self.telemetry is not None:
            await self.telemetry.start()

    def _on_slo_alert(self, alert) -> None:
        """SLO edge -> flight recorder: a FIRE is exactly the incident the
        blackbox exists for (the clear is history, not an emergency)."""
        if alert.transition == TRANSITION_FIRE:
            self.flightrec.trigger(TRIGGER_SLO_ALERT, alert.to_dict())

    def _on_worker_protocol_event(self, kind: str, detail: dict) -> None:
        """Worker-handle digest feed for the flight recorder's ring; an
        epoch-fence refusal additionally triggers a dump — stale traffic
        arriving at a live master means a failover just happened and the
        predecessor's final moments are worth keeping."""
        self.flightrec.record_event(kind, **detail)
        if kind == "stale_epoch_refusal":
            self.flightrec.trigger(TRIGGER_EPOCH_FENCE, detail)

    def _healthz_view(self) -> dict:
        view = {
            "role": "master",
            "workers_connected": len(self.workers),
            "workers_live": len(self.live_workers()),
            "job_started": self._job_started,
        }
        if self.epoch is not None:
            view["epoch"] = self.epoch
        return view

    async def _shutdown_server(self) -> None:
        """Stop the writer, cancel, close worker sockets, close the server."""
        if self.telemetry is not None:
            await self.telemetry.stop()
        await self.loopmon.stop()
        await self._history_sampler.stop()
        if self._snapshot_writer is not None:
            await self._snapshot_writer.stop()
        self.cancellation.cancel()
        # Close worker sockets BEFORE wait_closed(): since 3.12,
        # Server.wait_closed() waits for every live connection handler.
        for worker in list(self.workers.values()):
            await worker.shutdown()
        self._server.close()
        try:
            await asyncio.wait_for(self._server.wait_closed(), 5.0)
        except asyncio.TimeoutError:
            logger.warning("Server close timed out; continuing shutdown.")
        # Let deferred incident bundles land before the loop goes away.
        await self.flightrec.drain()
        if self.ledger is not None:
            if self.ledger_appender is not None:
                await self.ledger_appender.stop()
            try:
                await asyncio.to_thread(self.ledger.close)
            except OSError as e:
                logger.warning("Ledger close failed: %s", e)

    async def initialize_server_and_run_job(
        self,
    ) -> tuple[MasterTrace, list[tuple[str, WorkerTrace]]]:
        """Bind, run the job to completion, and collect all traces."""
        await self._bind_server()
        try:
            master_trace = await self._wait_for_workers_and_run_job()
            with self.span_tracer.span("collect traces", cat="master", track="job"):
                worker_traces = await self._collect_worker_traces()
            return master_trace, worker_traces
        finally:
            await self._shutdown_server()

    def live_workers(self) -> list[WorkerHandle]:
        return [w for w in self.workers.values() if not w.is_dead]

    def serving_workers(self) -> list[WorkerHandle]:
        """The live workers whose socket is up: those a pass may hand work
        to or ask anything of. A silent one (``WorkerHandle.is_silent``)
        is live, keeps its queue for the reconnect window, and is left be."""
        return [w for w in self.live_workers() if not w.is_silent]

    def _active_states(self) -> list[ClusterManagerState]:
        """Every frame table a worker may hold units of (the scheduler
        subclass: its running jobs')."""
        return [self.state] if self.state is not None else []

    def _jobs_view(self) -> dict:
        """Per-job live view folded into ``cluster_view()['jobs']`` (and
        with it into ``metrics-live.json``). Single-job masters report
        their one job with a trivially-full share; the scheduler subclass
        reports every submission with its fair-share targets."""
        if self.state is None:
            return {}
        return {
            self.state.job.job_name: {
                **job_state_view(self.state),
                "state": (
                    "finished" if self.state.all_frames_finished()
                    else ("running" if self._job_started else "waiting")
                ),
                "share_target": 1.0,
                "share_achieved": 1.0,
            }
        }

    def cluster_view(self) -> dict:
        """Live cluster-wide extras for the metrics snapshot.

        Combines the master's own frame-table view (all jobs' frame tables
        summed) with the most recent compact metrics payload each worker
        piggybacked on its heartbeat pong, plus their ``merge_wire``
        aggregation, and a per-job ``jobs`` section.
        """
        worker_payloads = {
            pm.worker_id_to_string(w.worker_id): w.latest_worker_metrics
            for w in self.workers.values()
            if w.latest_worker_metrics is not None
        }
        jobs_view = self._jobs_view()
        view: dict = {
            "cluster": {
                "frames_total": sum(
                    v["frames_total"] for v in jobs_view.values()
                ),
                "frames_finished": sum(
                    v["frames_finished"] for v in jobs_view.values()
                ),
                "frames_pending": sum(
                    v["frames_pending"] for v in jobs_view.values()
                ),
                "workers": {
                    pm.worker_id_to_string(w.worker_id): {
                        "queue_depth": len(w.queue),
                        "is_dead": w.is_dead,
                        "state": w.state_name,
                        "frames_stolen": w.frames_stolen_count,
                    }
                    for w in self.workers.values()
                },
            },
            "jobs": jobs_view,
        }
        prediction = self.cost_service.prediction_view()
        if prediction.get("samples_observed") or prediction.get("predictions"):
            view["prediction"] = prediction
        if self.speculation.config.enabled or self.speculation.launched_total:
            view["speculation"] = self.speculation.view()
        if self.slo.tracked():
            view["slo"] = self.slo.view()
        if self.flightrec.triggers or self.flightrec.dumps:
            view["flight"] = self.flightrec.view()
        if worker_payloads:
            view["worker_metrics"] = worker_payloads
            # Payloads crossed the wire from workers we don't control;
            # decode only shape-checks the top level, so a version-skewed
            # worker must degrade the aggregate view, not kill persistence.
            try:
                view["cluster_metrics"] = merge_wire(worker_payloads.values())
            except Exception as e:  # noqa: BLE001
                logger.warning("Worker metrics payloads failed to merge: %s", e)
        return view

    def timeline_other_data(self) -> dict | None:
        """Extra ``otherData`` for the merged cluster timeline (the
        scheduler subclass stamps its per-job summary; single-job masters
        add nothing)."""
        return None

    def cluster_timeline_processes(self) -> list[TimelineProcess]:
        """Everything the merged cluster timeline needs, master row first.

        One entry per process: the master's own span tracer (offset 0 by
        definition) plus, for every worker that piggybacked its span
        events on the job-finished response, those events tagged with the
        heartbeat estimator's offset for rebasing at export time. Workers that sent nothing (C++
        daemons, version skew) are simply absent — their causal links
        still show as master-side assign/result spans.
        """
        processes = [tracer_process(self.span_tracer, 0.0)]
        for worker in self.workers.values():
            collected = worker.collected_span_events
            if not collected or not isinstance(collected.get("events"), list):
                continue
            # The payload crossed the wire from a worker we don't control
            # and decode only shape-checks the top level: drop non-object
            # entries so a version-skewed peer degrades its own row instead
            # of killing the master's end-of-job artifact export.
            events = [e for e in collected["events"] if isinstance(e, dict)]
            if len(events) != len(collected["events"]):
                logger.warning(
                    "Worker %08x sent %d malformed span event(s); skipped.",
                    worker.worker_id,
                    len(collected["events"]) - len(events),
                )
            name = str(
                collected.get("process_name")
                or f"worker-{pm.worker_id_to_string(worker.worker_id)}"
            )
            try:
                dropped = int(collected.get("dropped") or 0)
            except (TypeError, ValueError):
                dropped = 0
            processes.append(
                TimelineProcess(
                    name=name,
                    events=events,
                    # Extrapolate the offset to NOW along the drift fit
                    # (collection time ~ the span timestamps' tail); with
                    # fewer than two samples this is the plain median.
                    offset_seconds=worker.clock_offset.offset_at(time.time()),
                    dropped=dropped,
                )
            )
        return processes

    # -- accept loop --------------------------------------------------------

    async def _on_tcp_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        """WS upgrade + 3-step application handshake.

        Reference: master/src/cluster/mod.rs:280-481.
        """
        try:
            ws = await asyncio.wait_for(
                websocket_accept(reader, writer), HANDSHAKE_TIMEOUT
            )
        except Exception as e:  # noqa: BLE001
            logger.debug("WS upgrade failed: %s", e)
            writer.close()
            return
        try:
            with self.span_tracer.span(
                "handshake", cat="transport", track="accept",
                args={"peer": ws.peer_address()},
            ):
                await asyncio.wait_for(self._perform_handshake(ws), HANDSHAKE_TIMEOUT)
        except Exception as e:  # noqa: BLE001
            logger.warning("Handshake with %s failed: %s", ws.peer_address(), e)
            ws.abort()

    async def _perform_handshake(self, ws: WebSocketConnection) -> None:
        if self.cancellation.is_cancelled():
            # Shutting down (or crashed and being torn down): a reconnect
            # accepted NOW would swap a live socket into a handle whose
            # reader tasks are already stopped, parking the worker on an
            # open-but-dead connection instead of letting it fail over.
            ws.abort()
            return
        # The optional epoch tells a reconnecting worker whether this is
        # the incarnation it lost (resume the session) or a successor
        # (re-announce fresh); epoch-less masters stay byte-identical.
        await ws.send_text(
            self._wire.encode(
                pm.MasterHandshakeRequest(PROTOCOL_VERSION, epoch=self.epoch)
            )
        )
        response = self._wire.decode(await ws.receive_text())
        if not isinstance(response, pm.WorkerHandshakeResponse):
            raise WebSocketClosed(f"Expected handshake response, got {type(response)}")

        if response.handshake_type == pm.HANDSHAKE_TYPE_FIRST_CONNECTION:
            await ws.send_text(
                self._wire.encode(pm.MasterHandshakeAcknowledgement(True))
            )
            await self._register_new_worker(
                response.worker_id, ws, prepares_jobs=response.prepares_jobs
            )
        elif response.handshake_type == pm.HANDSHAKE_TYPE_RECONNECTING:
            known = response.worker_id in self.workers
            await ws.send_text(
                self._wire.encode(pm.MasterHandshakeAcknowledgement(known))
            )
            if not known:
                # Reference: reconnect from an unknown worker is refused
                # (master/src/cluster/mod.rs:378-385).
                logger.warning(
                    "Refusing reconnect from unknown worker %08x", response.worker_id
                )
                ws.abort()
                return
            worker = self.workers[response.worker_id]
            if self.cancellation.is_cancelled():
                # Teardown raced the handshake: the handle's reader tasks
                # are stopping, so adopting this socket would strand the
                # worker — abort and let it retry against our successor.
                ws.abort()
                return
            worker.connection.replace_inner_connection(ws)
            self._reconnects_counter().inc(
                worker=pm.worker_id_to_string(response.worker_id)
            )
            worker.logger.info("Worker reconnected from %s", ws.peer_address())
            if not worker.is_dead:
                self._on_worker_reconnected(worker)
        else:
            raise WebSocketClosed(
                f"Unknown handshake type: {response.handshake_type!r}"
            )

    async def _register_new_worker(
        self, worker_id: int, ws: WebSocketConnection, *, prepares_jobs: bool = False
    ) -> None:
        if worker_id in self.workers:
            logger.warning(
                "Worker id collision (%08x); refusing duplicate.", worker_id
            )
            ws.abort()
            return
        connection = ReconnectableServerConnection(
            ws, metrics=self._transport_metrics
        )
        dispatch_delay_fn = None
        if self._dispatch_delay_fn is not None:
            manager_fn = self._dispatch_delay_fn
            dispatch_delay_fn = lambda frame_index: manager_fn(  # noqa: E731
                worker_id, frame_index
            )
        worker = WorkerHandle(
            worker_id,
            connection,
            self.state,
            on_dead=self._evict_worker,
            metrics=self.metrics,
            span_tracer=self.span_tracer,
            dispatch_delay_fn=dispatch_delay_fn,
            state_resolver=self._state_for_job,
            on_frame_complete=self.assembly.schedule,
            on_unit_latency=self.slo.observe_unit_latency,
            on_protocol_event=self._on_worker_protocol_event,
            epoch=self.epoch,
            prepares_jobs=prepares_jobs,
            wakeup=self.dispatch_wakeup,
            on_job_ready=self._on_worker_job_ready,
        )
        self.workers[worker_id] = worker
        self._reconnects_counter().inc(0.0, worker=pm.worker_id_to_string(worker_id))
        worker.start()
        self.dispatch_wakeup.set()
        logger.info(
            "Worker %08x connected from %s (%d/%d).",
            worker_id,
            ws.peer_address(),
            len(self.workers),
            self.job.wait_for_number_of_workers if self.job is not None else 0,
        )
        # Late joiners still learn which jobs have started (reference FIXME
        # at master/src/cluster/mod.rs:616-617) — replayed for EVERY active
        # job, which becomes load-bearing once several run concurrently.
        await self._announce_active_jobs(worker)

    async def _announce_active_jobs(self, worker: WorkerHandle) -> None:
        for trace_id, job_id, job in self._active_job_announcements():
            await worker.send_job_started(trace_id=trace_id, job_id=job_id, job=job)

    def _on_worker_job_ready(
        self, worker: WorkerHandle, job_name: str, job_id: str | None
    ) -> None:
        """A worker reported a job ready (the scheduler service's hook)."""

    def _reconnects_counter(self):
        return self.metrics.counter(
            "master_worker_reconnects_total",
            "Reconnect handshakes accepted from known workers (0 from a "
            "worker's first connection)",
            labels=("worker",),
        )

    def _on_worker_reconnected(self, worker: WorkerHandle) -> None:
        """A silent worker came back inside its window, with its id and
        its queue (the scheduler service's hook)."""
        self.dispatch_wakeup.set()

    async def _evict_worker(self, worker: WorkerHandle, reason: str) -> None:
        """Return a dead worker's units to the pool so its jobs can finish:
        every unit whose LIVE assignment is with it, by the frame tables.
        That is what its mirror holds (queued, rendering, saving: a unit is
        mirrored until its result is taken) and, beyond the mirror, a unit
        claimed for it whose queue-add was never acknowledged. Units its
        mirror holds for another's assignment (a speculative twin, a ghost
        copy from a superseded dispatch) stay where they are: requeueing
        those would put a unit in play twice while its primary renders it."""
        logger.warning("Evicting worker %08x: %s", worker.worker_id, reason)
        self.flightrec.trigger(
            TRIGGER_WORKER_EVICTION,
            {
                "worker": pm.worker_id_to_string(worker.worker_id),
                "reason": reason,
                "queued_units": len(worker.queue),
            },
        )
        for state in self._active_states():
            held = [
                unit for unit, holder in state.in_flight_units().items()
                if holder == worker.worker_id
            ]
            for unit in held:
                state.return_frame_to_pending(unit, "eviction")
            if held:
                # A write the worker's death cut leaves its temporary file
                # beside the frames; the job is not reported finished
                # before those are gone (the assembly barrier).
                self.assembly.schedule_cut_write_sweep(
                    state, sorted({unit.frame_index for unit in held})
                )
        # No ghost assignments: a dead worker's mirror must not keep
        # offering steal candidates (or claim queue depth) for frames that
        # just went back to the pool.
        worker.queue.clear()
        self.dispatch_wakeup.set()

    # -- job execution ------------------------------------------------------

    async def _wait_for_workers_and_run_job(self) -> MasterTrace:
        target = self.job.wait_for_number_of_workers
        logger.info("Waiting for %d workers to connect...", target)
        warmup_task: asyncio.Task | None = None
        strategy = self.job.frame_distribution_strategy
        if strategy.strategy_type == "tpu-batch":
            # Compile the auction kernel while workers connect so the first
            # scheduling tick doesn't pay XLA compilation inside the job.
            from tpu_render_cluster.master.tpu_batch import (
                RATE_TARGET_CAP,
                scaled_slot_cap,
            )
            from tpu_render_cluster.ops.assignment import warmup

            assert strategy.tpu_batch is not None
            # Warm up to the tick loop's scaled slot cap — warming only
            # MAX_SLOTS_PER_TICK would clamp >64-worker clusters back to
            # 128 slots/tick — bounded by the cluster's actual slot demand
            # (target-or-rate-cap per worker).
            demand_bound = max(
                strategy.tpu_batch.target_queue_size, RATE_TARGET_CAP
            ) * max(1, target)
            max_slots = min(scaled_slot_cap(target), demand_bound)
            warmup_task = asyncio.create_task(asyncio.to_thread(warmup, max_slots))
        with self.span_tracer.span(
            "barrier wait", cat="master", track="job", args={"target": target}
        ):
            try:
                while len(self.workers) < target:
                    if self.cancellation.is_cancelled():
                        raise RuntimeError("Cancelled while waiting for workers.")
                    await asyncio.sleep(BARRIER_POLL_SECONDS)
                if warmup_task is not None:
                    try:
                        await warmup_task
                    except Exception as e:  # noqa: BLE001 - latency opt, not fatal
                        logger.warning(
                            "Auction warmup failed (%s); first ticks will pay "
                            "compilation lazily.",
                            e,
                        )
            except BaseException:
                if warmup_task is not None and not warmup_task.done():
                    warmup_task.cancel()
                raise
        logger.info("All %d workers connected; starting job.", target)

        self._job_started = True
        for worker in self.live_workers():
            await worker.send_job_started()
        if self._replay_stitch_frames:
            # Tiled failover edge: every tile of these frames landed under
            # the predecessor but the stitch never did — re-schedule it
            # from the tile files on disk before new results interleave.
            for frame_index in self._replay_stitch_frames:
                self.assembly.schedule(self.state, frame_index)
            self._replay_stitch_frames = []

        self.metrics.gauge(
            "master_job_units", "Work units in the job's frame table"
        ).set(len(self.state.frames))
        start = time.time()
        self.slo.register_job(self.job, started_at=start)
        with self.span_tracer.span(
            "run job",
            cat="master",
            track="job",
            args={"strategy": strategy.strategy_type, "frames": len(self.state.frames)},
        ):
            # Speculation sidecar: strategy-agnostic tail hedging (no-op
            # unless TRC_SPECULATION enabled). Runs beside the strategy so
            # the reference dispatch loops stay untouched.
            spec_task = asyncio.create_task(
                speculation_loop(
                    self.job,
                    self.state,
                    self.live_workers,
                    self.cancellation,
                    self.speculation,
                ),
                name="speculation-loop",
            )
            # SLO sidecar: periodic burn/deadline evaluation while the
            # strategy runs (only for jobs that declared objectives).
            slo_task = (
                asyncio.create_task(
                    slo_loop(self.slo, self.state, self.cancellation),
                    name="slo-loop",
                )
                if self.job.slo is not None
                else None
            )
            try:
                await run_strategy(
                    self.job,
                    self.state,
                    self.live_workers,
                    self.cancellation,
                    cost_service=self.cost_service,
                    wakeup=self.dispatch_wakeup,
                )
                # Let the sidecar settle open races (outcomes accounted,
                # losers unqueued) before the finalization sweep audits
                # the mirrors; it exits promptly once all frames finished.
                await spec_task
            finally:
                if not spec_task.done():
                    spec_task.cancel()
                    await asyncio.gather(spec_task, return_exceptions=True)
                if slo_task is not None:
                    slo_task.cancel()
                    await asyncio.gather(slo_task, return_exceptions=True)
                # Final SLO evaluation at the job's true end time — the
                # deadline verdict and the closing attainment are stamped
                # whether the strategy finished or raised.
                self.slo.finish_job(self.job.job_name)
                if self.state.failed_reason:
                    # Deterministic unit failure killed the job: dump the
                    # window leading up to it while the evidence is warm.
                    self.flightrec.trigger(
                        TRIGGER_JOB_FAILURE,
                        {
                            "job": self.job.job_name,
                            "reason": self.state.failed_reason,
                        },
                    )
                # Accepted late results can finish a unit while its
                # re-dispatched twin still sits queued on a live worker;
                # the job is over, so those mirror entries are ghosts now
                # — sweep them (closing their flows) before anything
                # audits the mirrors. Tiled jobs: the last tile's
                # finished event schedules the frame's stitch
                # asynchronously — completed frames' stitches must land
                # on disk even when the strategy RAISES (a failed job
                # must not abandon mid-write assembly tasks).
                for worker in self.live_workers():
                    worker.sweep_finished_units(self._state_for_job)
                await self.assembly.drain()
        finish = time.time()
        if not self.state.all_frames_finished():
            raise RuntimeError("Strategy exited before all frames finished.")
        if self.ledger_appender is not None:
            # Ordered AFTER every queued unit append; drained so the
            # journal's lifecycle closure is durable before we report the
            # job finished (the same point the synchronous append gave).
            self.ledger_appender.schedule(
                self.ledger.append_job_finished, self.job.job_name
            )
            await self.ledger_appender.drain()
        logger.info("All frames finished in %.2f s.", finish - start)
        return MasterTrace(job_start_time=start, job_finish_time=finish)

    async def _collect_worker_traces(self) -> list[tuple[str, WorkerTrace]]:
        """Gather traces; key format ``<worker_id:08x>-<addr>``.

        Reference: master/src/cluster/mod.rs:514-541.
        """
        traces: list[tuple[str, WorkerTrace]] = []
        for worker in self.workers.values():
            worker.cancel_heartbeat()
            if worker.is_dead:
                logger.warning(
                    "Skipping trace collection for dead worker %08x.",
                    worker.worker_id,
                )
                continue
            try:
                trace = await worker.finish_job_and_get_trace()
            except Exception as e:  # noqa: BLE001
                logger.error(
                    "Could not collect trace from %08x: %s", worker.worker_id, e
                )
                continue
            name = f"{pm.worker_id_to_string(worker.worker_id)}-{worker.connection.last_known_address}"
            traces.append((name, trace))
        return traces
