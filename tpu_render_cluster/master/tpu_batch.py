"""The `tpu-batch` scheduler: cost-matrix assignment solved on TPU.

New in this build (the north-star scheduler from BASELINE.md): each
scheduling tick gathers every worker's queue deficit into a pool of *slots*
(worker x queue position), predicts the completion time of putting a frame
into each slot from a joint cost model — a per-worker speed EMA times a
per-frame complexity factor interpolated over frame index (scenes are
animated, so cost varies smoothly with the frame) — and solves the
frame->slot min-cost assignment with the JAX auction kernel
(tpu_render_cluster/ops/assignment.py). An opportunity-cost gate drops
assignments the rest of the cluster could finish sooner than the chosen
slot, which keeps the job tail off the slowest worker. Assignments are
issued as the same ``request_frame-queue_add`` RPCs the reference
strategies use, so workers can't tell the schedulers apart.

When the pending pool runs dry it degrades to dynamic-strategy stealing
(reference semantics: master/src/cluster/strategies.rs:250-405), which also
covers the cold-start case where no frame-time history exists yet.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import TYPE_CHECKING, Sequence

import numpy as np

from tpu_render_cluster.jobs.models import (
    BlenderJob,
    DynamicStrategyOptions,
    TpuBatchStrategyOptions,
)
from tpu_render_cluster.jobs.tiles import WorkUnit, unit_pixel_fraction
from tpu_render_cluster.master.state import ClusterManagerState
from tpu_render_cluster.master.strategies import (
    check_job_failed,
    find_busiest_worker_and_frame_to_steal,
    steal_frame,
)

# The model classes grew into a first-class subsystem (offline training,
# persistence, the shared online service) and moved to sched/cost_model.py;
# re-exported here because this was their original definition site.
from tpu_render_cluster.sched.cost_model import (  # noqa: F401 (re-exports)
    DEFAULT_FRAME_TIME_GUESS,
    CostModelService,
    FrameComplexityModel,
    JointCostModel,
    WorkerCostModel,
    load_cost_model_from_env,
)
from tpu_render_cluster.utils.cancellation import CancellationToken

if TYPE_CHECKING:
    from tpu_render_cluster.master.worker_handle import WorkerHandle

logger = logging.getLogger(__name__)

TPU_BATCH_TICK = 0.05
# Each worker's queue is sized to cover this many seconds of predicted work
# (bounded below by 1 and above by RATE_TARGET_CAP), so a fast worker's
# queue holds several ticks of frames while a slow worker holds one or two.
# A uniform target starves fast workers: they drain the whole queue within
# a tick and idle until the next one.
RATE_TARGET_LOOKAHEAD = 0.25
RATE_TARGET_CAP = 16
# Hard bound on slots considered per tick: keeps the auction matrix inside
# the pre-compiled bucket sizes (ClusterManager warms up to this many) and
# bounds per-tick work on huge clusters; later workers simply get topped up
# on the next tick.
MAX_SLOTS_PER_TICK = 128


def unit_complexity_map(
    units: Sequence[WorkUnit],
    complexity_model: FrameComplexityModel,
    tile_grid: tuple[int, int] | None,
) -> dict[WorkUnit, float]:
    """Per-UNIT complexity: the frame's predicted factor scaled by the
    unit's pixel fraction.

    The complexity model stays keyed by FRAME index (tiles of one frame
    share the scene, so they share the frame's factor), but a quarter-
    frame tile is a quarter of the work — pricing a ``(frame, tile)``
    unit at the whole frame's cost uniformly overpriced tiled jobs (and
    distorted the makespan gate's unit arithmetic).
    """
    frame_predictions = complexity_model.predict_many(
        sorted({unit.frame_index for unit in units})
    )
    return {
        unit: frame_predictions[unit.frame_index]
        * unit_pixel_fraction(unit, tile_grid)
        for unit in units
    }


def build_cost_matrix(
    frames: Sequence[int],
    slots: Sequence[tuple["WorkerHandle", int]],
    cost_model: WorkerCostModel,
    *,
    frame_complexity: dict[int, float] | None = None,
) -> np.ndarray:
    """cost[i, j] = predicted completion time of frame i in slot j.

    A slot is (worker, position-in-queue): completion = (current queue length
    + position + 1) * predicted frame time on that worker, scaled by the
    frame's complexity factor when a per-frame predictor is available.
    """
    cost = np.zeros((len(frames), len(slots)), dtype=np.float32)
    slot_base = np.array(
        [
            (len(worker.queue) + position + 1) * cost_model.predict(worker.worker_id)
            for worker, position in slots
        ],
        dtype=np.float32,
    )
    for i, frame_index in enumerate(frames):
        scale = 1.0
        if frame_complexity is not None:
            scale = frame_complexity.get(frame_index, 1.0)
        cost[i] = slot_base * scale
    return cost


def scaled_slot_cap(worker_count: int) -> int:
    """Per-tick slot budget for a cluster of ``worker_count`` workers.

    A fixed cap becomes the assignment throughput ceiling on many-worker
    clusters. Shared by the tick loop (which clamps it to the warmed
    auction buckets) and the ClusterManager's barrier-time warmup (which
    must compile buckets covering it, or warmed_max_slots() clamps the
    tick right back to the fixed cap)."""
    return max(MAX_SLOTS_PER_TICK, 2 * max(1, worker_count))


def makespan_horizon(
    rest_units: float, others_rate: float, fastest_speed: float, frame_complexity: float
) -> float:
    """Latest acceptable completion time for a candidate assignment.

    ``rest_units`` is everything the REST of the cluster still has to chew
    through (pending pool + other queues, in complexity units) and
    ``others_rate`` their combined rate; an assignment whose predicted
    completion exceeds this drain window (plus one fastest-worker frame of
    slack) would make its worker the job's tail, so the gate skips it.
    Pure so the gate's decision structure is unit-testable without a
    cluster (tests/test_tpu_batch_model.py).
    """
    rest_seconds = rest_units / others_rate if others_rate > 0 else float("inf")
    return rest_seconds + fastest_speed * frame_complexity


def _as_dynamic_options(options: TpuBatchStrategyOptions) -> DynamicStrategyOptions:
    return DynamicStrategyOptions(
        target_queue_size=options.target_queue_size,
        min_queue_size_to_steal=options.min_queue_size_to_steal,
        min_seconds_before_resteal_to_elsewhere=options.min_seconds_before_resteal_to_elsewhere,
        min_seconds_before_resteal_to_original_worker=options.min_seconds_before_resteal_to_original_worker,
    )


async def tpu_batch_strategy(
    job: BlenderJob,
    state: ClusterManagerState,
    workers_fn,
    cancellation: CancellationToken,
    options: TpuBatchStrategyOptions,
    *,
    cost_service: CostModelService | None = None,
) -> None:
    from tpu_render_cluster.ops.assignment import solve_assignment

    # The model is shared master state now (sched/cost_model.py): the
    # manager passes its service so the speculation loop and a persisted
    # TRC_COST_MODEL snapshot warm-start the auction; standalone callers
    # (tests) still get a private cold instance.
    if cost_service is None:
        cost_service = CostModelService(
            load_cost_model_from_env(), alpha=options.cost_ema_alpha
        )
    cost_model = cost_service.model
    scene = CostModelService.scene_key(job)
    complexity_model = cost_model.complexity_model(scene)
    # This loop runs one job: every completion observation is priced
    # against it (the service keys scene + tile grid off the job).
    job_for = lambda _job_name: job  # noqa: E731
    dynamic_options = _as_dynamic_options(options)
    starved_since: float | None = None  # first fully-gated tick of a streak
    # A tiled job's pending pool is counted in UNITS; the model-wide mean
    # complexity is frame-equivalent, so pool work scales by the fraction.
    pool_unit_fraction = 1.0 / job.tiles_per_frame()

    while not cancellation.is_cancelled():
        if state.all_frames_finished():
            return
        check_job_failed(state)
        workers = [w for w in workers_fn() if not w.is_dead]
        if not workers:
            await asyncio.sleep(TPU_BATCH_TICK)
            continue

        # Feed the cost model with fresh completions (the shared service
        # consumes each observation exactly once, normalizes tile pixel
        # fractions, and accounts prediction error).
        cost_service.ingest(workers, job_for)

        # Collect slots from queue deficits, with per-worker targets scaled
        # to each worker's predicted rate (uniform targets until history
        # arrives — the cold-start case falls back to eager-coarse shape).
        # Units are (frame, tile) under a tile grid; the complexity model
        # stays keyed by FRAME index (tiles of one frame share the scene,
        # so they share the frame's complexity factor), scaled per unit by
        # its pixel fraction (unit_complexity_map).
        upcoming = state.pending_units(limit=2 * RATE_TARGET_CAP)
        upcoming_complexity = unit_complexity_map(
            upcoming, complexity_model, job.tile_grid
        )
        batch_mean_complexity = (
            float(np.mean(list(upcoming_complexity.values())))
            if upcoming
            else 1.0
        )
        # Slots are interleaved breadth-first by position (every worker's
        # front slot before any second slot): the slot-cap truncation below
        # must never hide an idle worker's front slot behind another
        # worker's deep queue positions — at the job tail that starves the
        # scheduler (only deep slots survive, the makespan gate rejects
        # every assignment, and the job hangs with frames pending).
        deficits: list[tuple["WorkerHandle", int]] = []
        for worker in workers:
            if cost_model.worker_speed.has_history(worker.worker_id):
                frame_seconds = max(
                    1e-6,
                    cost_model.worker_speed.predict(worker.worker_id)
                    * batch_mean_complexity,
                )
                # The configured target is a floor: a worker must always
                # hold at least one buffered frame beyond the one it is
                # rendering, or it idles for a full master round-trip after
                # every frame (utilization collapses to ~50% on fast
                # backends). Rate-scaling only ever deepens the queue for
                # workers that drain faster than the lookahead window.
                target = min(
                    max(
                        options.target_queue_size,
                        int(np.ceil(RATE_TARGET_LOOKAHEAD / frame_seconds)),
                    ),
                    max(options.target_queue_size, RATE_TARGET_CAP),
                )
            else:
                # Cold start: commit conservatively until the model has seen
                # this worker render — dumping a full target_queue_size onto
                # a worker of unknown speed parks frames on what may be the
                # slowest node, and short jobs never recover via stealing.
                target = min(2, options.target_queue_size)
            deficits.append((worker, max(0, target - len(worker.queue))))
        slots: list[tuple["WorkerHandle", int]] = []
        max_deficit = max((d for _, d in deficits), default=0)
        for position in range(max_deficit):
            for worker, deficit in deficits:
                if position < deficit:
                    slots.append((worker, position))
        # Stay within pre-compiled auction buckets (late-joining workers can
        # push the slot count past what the barrier-time warmup covered);
        # excess workers are topped up on later ticks.
        from tpu_render_cluster.ops.assignment import warmed_max_slots

        # Scale the per-tick budget with the cluster (C++ twin: slot_cap
        # in tpu_batch_loop). Warmed auction buckets still bound it: an
        # unwarmed size would compile mid-job.
        slot_cap = scaled_slot_cap(len(workers))
        if 0 < warmed_max_slots() < slot_cap:
            slot_cap = warmed_max_slots()
        del slots[slot_cap:]

        if slots:
            units = state.pending_units(limit=len(slots))
            if units:
                complexity = unit_complexity_map(
                    units, complexity_model, job.tile_grid
                )
                cost = build_cost_matrix(
                    units,
                    slots,
                    cost_model.worker_speed,
                    frame_complexity=complexity,
                )
                assignment = solve_assignment(cost)

                # Makespan-balance gate: skip an assignment whose predicted
                # completion exceeds the time the OTHER workers need to
                # drain the rest of the pool — queueing it there can only
                # lengthen the makespan. A slow worker still receives
                # frames it can finish within the others' drain window
                # (keeping tail delay low), but never a frame that would
                # make it the job's tail. The fastest worker's own front
                # slot always passes (completion == slack term), so the job
                # always makes progress.
                speeds = {
                    worker.worker_id: cost_model.worker_speed.predict(worker.worker_id)
                    for worker in workers
                }
                cluster_rate = sum(1.0 / max(1e-6, s) for s in speeds.values())
                # Work is measured in complexity units throughout: the pool
                # via the model-wide mean (pools can be 14400 frames — too
                # many to predict individually each tick), queues via the
                # sum of per-frame predictions (queues are small), and the
                # candidate frame via its own prediction — so the
                # subtraction in rest_units below is unit-consistent.
                pool_units = (
                    state.pending_count()
                    * complexity_model.mean_observed()
                    * pool_unit_fraction
                )
                mirrored_complexity = unit_complexity_map(
                    [
                        f.unit
                        for worker in workers
                        for f in worker.queue.all_frames()
                    ],
                    complexity_model,
                    job.tile_grid,
                )
                queued_units = {
                    worker.worker_id: sum(
                        mirrored_complexity[f.unit]
                        for f in worker.queue.all_frames()
                    )
                    for worker in workers
                }
                total_queued_units = sum(queued_units.values())
                fastest_speed = min(speeds.values())

                # Claim frames synchronously, then issue the add-RPCs
                # concurrently (the reference queues serially in the tick
                # loop; batching the RPCs keeps tick latency flat as the
                # cluster grows).
                async def assign(unit, worker: "WorkerHandle") -> None:
                    try:
                        await worker.queue_frame(job, unit)
                    except Exception as e:  # noqa: BLE001
                        logger.warning(
                            "tpu-batch: failed to queue unit %s on %08x: %s",
                            unit.label,
                            worker.worker_id,
                            e,
                        )
                        state.return_frame_to_pending(unit, "dispatch_failed")

                tasks = []
                for i, unit in enumerate(units):
                    worker, _position = slots[int(assignment[i])]
                    others_rate = cluster_rate - 1.0 / max(
                        1e-6, speeds[worker.worker_id]
                    )
                    # Everything the rest of the cluster still has to chew
                    # through: the pending pool plus their own queues.
                    rest_units = max(
                        0.0, pool_units - complexity[unit]
                    ) + (total_queued_units - queued_units[worker.worker_id])
                    horizon = makespan_horizon(
                        rest_units, others_rate, fastest_speed, complexity[unit]
                    )
                    if cost[i, int(assignment[i])] > horizon:
                        continue  # leave pending; a better slot will open
                    state.mark_frame_as_queued(unit, worker.worker_id, time.time())
                    tasks.append(assign(unit, worker))
                if not tasks and units:
                    # Forced progress: the gate's invariant is that the
                    # fastest worker's front slot always passes, but the
                    # auction may return an epsilon-suboptimal matching
                    # that never proposes that pair — gating the whole
                    # tick, every tick (observed in the C++ master at the
                    # tail of a 14400f x 40w run). Queue the cheapest
                    # frame on the GLOBALLY fastest worker (the one the
                    # invariant is about — cannot lengthen the makespan).
                    # When that worker's queue is full the gate may be
                    # right to wait for it to drain, so a slower worker
                    # is only settled for after the starvation persists —
                    # transient gate rejections stay respected.
                    if starved_since is None:
                        starved_since = time.time()
                    eligible = [
                        w for w in workers
                        if len(w.queue) < max(1, options.target_queue_size)
                    ]
                    if eligible:
                        fastest = min(
                            eligible, key=lambda w: speeds[w.worker_id]
                        )
                        fastest_overall = min(
                            workers, key=lambda w: speeds[w.worker_id]
                        )
                        if (
                            fastest is fastest_overall
                            or time.time() - starved_since > 1.0
                        ):
                            unit = min(units, key=lambda u: complexity[u])
                            state.mark_frame_as_queued(
                                unit, fastest.worker_id, time.time()
                            )
                            tasks.append(assign(unit, fastest))
                if tasks:
                    # The streak is CONSECUTIVE fully-gated ticks only; any
                    # tick that queues work (and, below, any tick with
                    # nothing to assign) resets it — a stale timestamp from
                    # an earlier streak must not let the fallback fire
                    # instantly and park a tail frame on a slow worker.
                    starved_since = None
                await asyncio.gather(*tasks)
                await asyncio.sleep(TPU_BATCH_TICK)
                continue

            starved_since = None
            # Pending pool dry -> steal like the dynamic strategy.
            workers_sorted = sorted(workers, key=lambda w: len(w.queue))
            for thief in workers_sorted:
                if len(thief.queue) >= options.target_queue_size:
                    continue
                found = find_busiest_worker_and_frame_to_steal(
                    thief, workers_sorted, dynamic_options
                )
                if found is None:
                    break
                victim, frame = found
                await steal_frame(job, state, thief, victim, frame.unit)

        if not slots:
            starved_since = None  # no slots this tick: not a gated streak
        await asyncio.sleep(TPU_BATCH_TICK)
