"""Cluster manager state: the global work-unit table + worker registry.

Semantics follow the reference's ``ClusterManagerState`` frame status machine
(Pending -> QueuedOnWorker -> RenderingOnWorker -> Finished, with steal
transitions back to Queued — reference: master/src/cluster/state.rs:13-130),
but the data structures are scale-fixed: the reference linearly scans a
``Vec`` of 14 400 frames on every 50 ms tick (state.rs:63-80, flagged in
SURVEY.md §5.7); here pending units live in a deque and finished units in
a counter, making ``next_pending_unit``/``all_frames_finished`` O(1).

PR 7 extends the unit of distribution from a whole frame to
``WorkUnit(frame_index, tile)`` (jobs/tiles.py): for a tiled job every
frame splits into grid tiles that dispatch, steal, evict, and dedup
independently, and a per-frame ASSEMBLY ledger tracks which tiles have
landed so the frame-level result (the stitched image, the "frame done"
event) fires exactly once — when the last tile lands. Whole-frame jobs
(``tile is None``) behave exactly as before.
"""

from __future__ import annotations

import enum
import time
from collections import deque
from dataclasses import dataclass

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.protocol.messages import generate_trace_id

# Why a unit left the worker that held it without a result (``handbacks``).
HANDBACK_CAUSES = (
    "preemption",  # fair share unqueued it for a starved job
    "steal",  # another worker took it over
    "eviction",  # its worker died
    "drain",  # its worker said goodbye and returned it
    "error",  # it errored on the worker and is rescheduled
    "dispatch_failed",  # its queue-add was not acknowledged, or was superseded
)


class FrameStatus(enum.Enum):
    PENDING = "pending"
    QUEUED_ON_WORKER = "queued"
    RENDERING_ON_WORKER = "rendering"
    FINISHED = "finished"


@dataclass
class FrameRecord:
    unit: WorkUnit
    status: FrameStatus = FrameStatus.PENDING
    worker_id: int | None = None
    queued_at: float | None = None
    # Worker the unit was last stolen FROM (provenance for the
    # resteal-to-original-worker anti-thrash timer, reference:
    # master/src/cluster/state.rs:13-24, strategies.rs:155-191).
    stolen_from: int | None = None
    stolen_at: float | None = None
    # Errored results received for this unit across all its assignments.
    # A deterministic failure (a backend that cannot render the unit at
    # all) would otherwise requeue-and-error forever; the cap turns the
    # livelock into a job failure (worker_handle -> failed_reason).
    errored_count: int = 0
    # The worker whose ok result finished the unit (the first one taken;
    # ``worker_id`` is the live assignment's, which a late result from a
    # superseded assignment does not move).
    finished_by: int | None = None

    @property
    def frame_index(self) -> int:
        return self.unit.frame_index

    @property
    def tile(self) -> int | None:
        return self.unit.tile


@dataclass
class SpeculationRecord:
    """One live speculative twin of an in-flight unit (master-internal).

    The PRIMARY assignment owns the frame record as usual; the TWIN is a
    byte-identical duplicate dispatch to a second worker, tracked only
    here (the wire and the C++ workers cannot tell a twin from any other
    assignment). ``winner_worker_id`` is stamped by the first accepted ok
    result — the dedup ledger absorbs the loser's copy — and the
    speculation loop (master/speculate.py) unqueues the loser and
    accounts the outcome.
    """

    unit: WorkUnit
    primary_worker_id: int
    twin_worker_id: int
    started_at: float
    predicted_primary_s: float
    predicted_twin_s: float
    winner_worker_id: int | None = None


class ClusterManagerState:
    """Per-job work-unit table; single event loop, so no locking is needed.

    One instance per RUNNING job: the single-job master owns exactly one,
    the multi-job scheduler (sched/manager.py) one per admitted job, with
    WorkerHandle routing worker events to the right instance by the
    reference ``job_name`` field every event already carries.
    """

    def __init__(self, job: BlenderJob) -> None:
        self.job = job
        # One trace id per job run: every assignment span and worker echo
        # carries it, so artifacts from different runs never alias
        # (protocol/messages.py TraceContext rides on this).
        self.trace_id: int = generate_trace_id()
        # Scheduler job id (sched/ only; None on the single-job path).
        # Guards job-name reuse: a late result stamped with a PREVIOUS
        # submission's job_id must not count against a new job that
        # happens to share the name.
        self.sched_job_id: str | None = None
        # Set when a unit exhausts its error budget: the strategy loops
        # surface it as a job failure (the scheduler cancels the job)
        # instead of spinning redispatch RPCs forever.
        self.failed_reason: str | None = None
        self.frames: dict[WorkUnit, FrameRecord] = {
            unit: FrameRecord(unit) for unit in job.work_units()
        }
        self._pending: deque[WorkUnit] = deque(job.work_units())
        self._finished_count = 0
        # Mutation counter, bumped by every frame transition (status OR
        # worker reassignment). The incremental WFQ (sched/wfq.py) keys
        # its per-job resync off this: a job whose version is unchanged
        # since the last tick cannot have changed demand, load, or the
        # worker placement its cost prediction depends on, so the tick
        # skips it entirely. Evictions, goodbyes, steals, late results,
        # and ledger replay all funnel through these transitions, so no
        # event source needs separate instrumentation.
        self.version: int = 0
        # When the first unit was handed to a worker, and when the newest
        # result was taken (the scheduler's per-job phases).
        self.first_queued_at: float | None = None
        self.last_finished_at: float | None = None
        # O(1) mirrors of the status population. ``_pending_live`` counts
        # frames whose STATUS is PENDING (the deque may briefly hold
        # stale or duplicate entries; status is the truth);
        # ``_in_flight_units`` maps each QUEUED/RENDERING unit to the
        # worker currently holding it — exactly the set the cost model
        # prices for a job's in-flight load, without an O(frames) scan.
        self._pending_live: int = len(self.frames)
        self._in_flight_units: dict[WorkUnit, int] = {}
        # Per-job exactly-once ledger, updated by WorkerHandle at the same
        # points as the global ``master_*_results_total`` counters so the
        # PR-4 chaos invariant (ok - duplicates == units_total) can be
        # audited PER JOB when several share the worker pool.
        self.ledger: dict[str, int] = {
            "ok_results": 0,
            "errored_results": 0,
            "duplicate_results": 0,
            "late_results": 0,
            "stale_results": 0,
            # Results refused because they carry a PREVIOUS master
            # incarnation's epoch (ha/: the fencing half of failover).
            "stale_epoch_results": 0,
        }
        # Write-ahead ledger sinks (ha/ledger.py, wired by a ledger-backed
        # master AFTER replay application so replayed units are not
        # re-journaled): called exactly once per unit/frame, on the same
        # transitions the in-memory ledger meters.
        self.on_unit_finished = None
        self.on_frame_assembled = None
        # Called with the cause of every entry of ``handbacks`` (the
        # scheduler service's ``sched_units_handed_back_total``).
        self.on_handback = None
        # Per-frame assembly ledger (tiled jobs): frame -> the set of tile
        # indices whose units reached FINISHED. A frame is assembly-ready
        # when the set reaches ``tiles_per_frame`` — each tile lands in it
        # exactly once because ``mark_frame_as_finished`` transitions each
        # unit to FINISHED exactly once (duplicates are absorbed upstream).
        self._tiles_per_frame = job.tiles_per_frame()
        self._assembly: dict[int, set[int]] = {}
        self.frames_assembled = 0
        # Live speculative twins keyed by unit (master/speculate.py): a
        # unit under speculation is dispatched on TWO workers at once;
        # first accepted ok result wins through the dedup ledger.
        self.speculations: dict[WorkUnit, SpeculationRecord] = {}
        # Per-unit queue-to-result latency of each unit's WINNING result
        # (exact, one float per unit): the p99 the predictive scheduler is
        # judged on (the chaos report's stats).
        self.unit_seconds: list[float] = []
        # Every time a unit left the worker that held it without a result:
        # (unit, worker it left, cause, wall time), ``cause`` one of
        # ``HANDBACK_CAUSES``. What the master reports of a unit that two
        # workers may have rendered (``JobManager.handbacks_view``): a
        # worker that had already taken the unit in hand renders it all
        # the same, and its copy is the duplicate the dedup seam absorbs.
        # It lives as long as the job is listed: the scheduler service
        # empties it when the job leaves its list of ended jobs.
        self.handbacks: list[tuple[WorkUnit, int | None, str, float]] = []

    # -- queries -----------------------------------------------------------

    def next_pending_unit(self) -> WorkUnit | None:
        """Peek the next pending work unit (O(1))."""
        while self._pending:
            unit = self._pending[0]
            if self.frames[unit].status is FrameStatus.PENDING:
                return unit
            self._pending.popleft()  # stale entry
        return None

    def all_frames_finished(self) -> bool:
        return self._finished_count >= len(self.frames)

    def finished_count(self) -> int:
        return self._finished_count

    def pending_count(self) -> int:
        """Frames whose status is PENDING (O(1): maintained counter)."""
        return self._pending_live

    def in_flight_count(self) -> int:
        """Units currently queued-on or rendering-on some worker — the
        quantity the fair-share scheduler meters per job (O(1))."""
        return len(self._in_flight_units)

    def in_flight_units(self) -> dict[WorkUnit, int]:
        """Live view of queued/rendering units -> holding worker id.
        Callers must not mutate it; the transitions below own it."""
        return self._in_flight_units

    def pending_units(self, limit: int | None = None) -> list[WorkUnit]:
        out = []
        for unit in self._pending:
            if self.frames[unit].status is FrameStatus.PENDING:
                out.append(unit)
                if limit is not None and len(out) >= limit:
                    break
        return out

    # -- assembly ledger (tiled jobs) --------------------------------------

    def tiles_landed(self, frame_index: int) -> int:
        """Tiles of ``frame_index`` that have reached FINISHED."""
        if self._tiles_per_frame == 1:
            # One unit per frame — but its KEY is tile 0 for a (valid)
            # 1x1 tiled job and tile None for an untiled one.
            unit = WorkUnit(
                frame_index, None if self.job.tile_grid is None else 0
            )
            record = self.frames.get(unit)
            return int(
                record is not None and record.status is FrameStatus.FINISHED
            )
        return len(self._assembly.get(frame_index, ()))

    def partially_assembled_frames(self) -> list[int]:
        """Frames with SOME but not all tiles landed — must be empty after
        any completed run (the no-ghost-frame chaos invariant; a cancelled
        job may legitimately hold some)."""
        return sorted(
            frame
            for frame, tiles in self._assembly.items()
            if 0 < len(tiles) < self._tiles_per_frame
        )

    def assembly_view(self) -> dict:
        """The ``assembly`` section of the per-job live view."""
        return {
            "tiles_per_frame": self._tiles_per_frame,
            "frames_assembled": self.frames_assembled,
            "frames_partial": len(self.partially_assembled_frames()),
        }

    # -- transitions -------------------------------------------------------
    #
    # Every transition accepts a bare int as a WHOLE-FRAME unit (the
    # pre-tiling call shape): normalization goes through one helper so
    # frame-keyed callers and tile-keyed callers cannot drift.

    @staticmethod
    def _as_unit(unit: "WorkUnit | int") -> WorkUnit:
        return WorkUnit(unit) if isinstance(unit, int) else unit

    def _retrack(self, record: FrameRecord, old: FrameStatus) -> None:
        """Fold one applied transition into the O(1) mirrors + version.

        Called AFTER the record's status/worker fields are updated. Every
        transition must come through here — the scheduler's incremental
        structures trust ``version`` to cover all demand/load/placement
        changes, including worker reassignments that keep the status.
        """
        new = record.status
        if old is FrameStatus.PENDING:
            if new is not FrameStatus.PENDING:
                self._pending_live -= 1
        elif new is FrameStatus.PENDING:
            self._pending_live += 1
        if (
            new
            in (FrameStatus.QUEUED_ON_WORKER, FrameStatus.RENDERING_ON_WORKER)
            and record.worker_id is not None
        ):
            self._in_flight_units[record.unit] = record.worker_id
        else:
            self._in_flight_units.pop(record.unit, None)
        self.version += 1

    def mark_frame_as_queued(
        self,
        unit: "WorkUnit | int",
        worker_id: int,
        queued_at: float,
        *,
        stolen_from: int | None = None,
        stolen_at: float | None = None,
    ) -> None:
        unit = self._as_unit(unit)
        record = self.frames[unit]
        if record.status is FrameStatus.FINISHED:
            raise ValueError(f"BUG: unit {unit.label} is already finished.")
        old = record.status
        record.status = FrameStatus.QUEUED_ON_WORKER
        record.worker_id = worker_id
        record.queued_at = queued_at
        if self.first_queued_at is None:
            self.first_queued_at = queued_at
        if stolen_from is not None:
            record.stolen_from = stolen_from
            record.stolen_at = stolen_at
            self._note_handback(unit, stolen_from, "steal", queued_at)
        if self._pending and self._pending[0] == unit:
            self._pending.popleft()
        self._retrack(record, old)

    def mark_frame_as_rendering(
        self, unit: "WorkUnit | int", worker_id: int
    ) -> None:
        unit = self._as_unit(unit)
        record = self.frames[unit]
        if record.status is FrameStatus.FINISHED:
            return  # late event after a race; harmless
        old = record.status
        record.status = FrameStatus.RENDERING_ON_WORKER
        record.worker_id = worker_id
        self._retrack(record, old)

    def mark_frame_as_finished(
        self, unit: "WorkUnit | int", by: int | None = None
    ) -> bool:
        """Transition a unit to FINISHED; returns True when this call
        completed its whole FRAME (every tile landed) — the exactly-once
        assembly trigger. Idempotent: repeated calls return False.
        ``by``: the worker whose result this is.
        """
        unit = self._as_unit(unit)
        record = self.frames[unit]
        if record.status is FrameStatus.FINISHED:
            return False
        old = record.status
        record.status = FrameStatus.FINISHED
        record.finished_by = by
        self._retrack(record, old)
        self._finished_count += 1
        self.last_finished_at = time.time()
        if self.on_unit_finished is not None:
            self.on_unit_finished(unit)
        if self._tiles_per_frame == 1:
            return True
        landed = self._assembly.setdefault(unit.frame_index, set())
        landed.add(unit.tile if unit.tile is not None else 0)
        return len(landed) >= self._tiles_per_frame

    def note_frame_assembled(self, frame_index: int) -> None:
        self.frames_assembled += 1
        # Fully-landed frames leave the partial map so the ghost-frame
        # audit is O(frames in flight), not O(job).
        self._assembly.pop(frame_index, None)
        if self.on_frame_assembled is not None:
            self.on_frame_assembled(frame_index)

    def _note_handback(
        self, unit: WorkUnit, worker_id: int | None, cause: str, at: float
    ) -> None:
        self.handbacks.append((unit, worker_id, cause, at))
        if self.on_handback is not None:
            self.on_handback(cause)

    def return_frame_to_pending(self, unit: "WorkUnit | int", cause: str) -> None:
        """Unit comes back to the pool (steal succeeded, render errored,
        or its worker died). Unlike the reference — where a dead worker's
        frames stay QueuedOnWorker forever (SURVEY.md §5.3) — this makes
        eviction recoverable. Idempotent: under fault races (an eviction
        and a failed dispatch both returning the same unit) the second
        call must not add a second pending entry. ``cause`` (one of
        ``HANDBACK_CAUSES``) is kept in ``handbacks``."""
        if cause not in HANDBACK_CAUSES:
            raise ValueError(f"unknown handback cause: {cause!r}")
        unit = self._as_unit(unit)
        record = self.frames[unit]
        if record.status in (FrameStatus.FINISHED, FrameStatus.PENDING):
            return
        self._note_handback(unit, record.worker_id, cause, time.time())
        old = record.status
        record.status = FrameStatus.PENDING
        record.worker_id = None
        record.queued_at = None
        self._pending.append(unit)
        self._retrack(record, old)
