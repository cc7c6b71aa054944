"""Frame-distribution strategies (the scheduler).

Behavioral contract from the reference (master/src/cluster/strategies.rs):

- **naive-fine** (strategies.rs:16-68): 50 ms tick; any worker with an empty
  queue receives exactly one pending frame. A pass also starts as soon as a
  result leaves a worker's queue empty (master/wakeup.py), so the tick is
  only what a lost signal costs.
- **eager-naive-coarse** (strategies.rs:70-150): 100 ms tick; every worker's
  queue is topped up to ``target_queue_size``.
- **dynamic** (strategies.rs:155-405): 50 ms tick; workers sorted by queue
  size ascending; each below-target worker gets one pending frame, or — when
  the pending pool is dry — steals one from the busiest worker. The steal
  candidate skips the first ``min_queue_size_to_steal`` entries (nearest to
  rendering), respects both anti-thrash resteal timers, and prefers the
  longest-queued frame; remove-vs-render races (``already-rendering`` /
  ``already-finished``) are tolerated by skipping the steal.

The selection helpers are pure functions over the queue mirrors so they are
unit-testable without a cluster (the reference never had such tests —
SURVEY.md §4).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import TYPE_CHECKING, Sequence

from tpu_render_cluster.jobs.models import (
    BlenderJob,
    DynamicStrategyOptions,
)
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.master.queue_mirror import FrameOnWorker
from tpu_render_cluster.master.state import ClusterManagerState, FrameStatus
from tpu_render_cluster.master.wakeup import DispatchWakeup
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.utils.cancellation import CancellationToken

if TYPE_CHECKING:
    from tpu_render_cluster.master.worker_handle import WorkerHandle

logger = logging.getLogger(__name__)

NAIVE_FINE_TICK = 0.05
EAGER_COARSE_TICK = 0.1
DYNAMIC_TICK = 0.05


# ---------------------------------------------------------------------------
# Pure steal-candidate selection (reference: strategies.rs:155-248)


def select_best_frame_to_steal(
    thief_worker_id: int,
    victim_queue: Sequence[FrameOnWorker],
    options: DynamicStrategyOptions,
    *,
    now: float | None = None,
) -> FrameOnWorker | None:
    """Pick the steal candidate from a victim's queue mirror.

    ``victim_queue`` must be the not-yet-rendering frames in queue order.
    Returns the oldest eligible frame at position >= ``min_queue_size_to_steal``,
    where eligibility requires the frame to have sat on the victim for at
    least the resteal-to-elsewhere timer (or the longer resteal-to-original
    timer when the thief is the worker it was originally stolen from).
    """
    now = time.time() if now is None else now
    best: FrameOnWorker | None = None
    for frame in victim_queue[options.min_queue_size_to_steal :]:
        since_queued = now - frame.queued_at
        if frame.stolen_from is not None and frame.stolen_from == thief_worker_id:
            if since_queued >= options.min_seconds_before_resteal_to_original_worker:
                if best is None or frame.queued_at < best.queued_at:
                    best = frame
            continue
        if since_queued >= options.min_seconds_before_resteal_to_elsewhere:
            if best is None or frame.queued_at < best.queued_at:
                best = frame
    return best


def find_busiest_worker_and_frame_to_steal(
    thief: "WorkerHandle",
    workers: Sequence["WorkerHandle"],
    options: DynamicStrategyOptions,
    *,
    now: float | None = None,
) -> tuple["WorkerHandle", FrameOnWorker] | None:
    """Find (victim, frame) — the biggest queue holding an eligible frame.

    Only queues strictly larger than ``min_queue_size_to_steal`` are
    considered (reference: strategies.rs:193-248).
    """
    best: tuple["WorkerHandle", int, FrameOnWorker] | None = None
    for victim in workers:
        if victim.worker_id == thief.worker_id or victim.is_dead:
            continue
        queue_size = len(victim.queue)
        if queue_size <= options.min_queue_size_to_steal:
            continue
        if best is not None and queue_size <= best[1]:
            continue
        candidate = select_best_frame_to_steal(
            thief.worker_id, victim.queue.queued_frames_in_order(), options, now=now
        )
        if candidate is not None:
            best = (victim, queue_size, candidate)
    if best is None:
        return None
    return best[0], best[2]


# ---------------------------------------------------------------------------
# Strategy loops


def check_job_failed(state: ClusterManagerState) -> None:
    """Raise when the job crossed its unit-error budget — called once
    per tick by every strategy loop so a deterministically-failing unit
    ends the job with a clear error instead of an endless redispatch
    spin (the scheduler's loop cancels the job instead of raising)."""
    if state.failed_reason is not None:
        raise RuntimeError(f"Job failed: {state.failed_reason}")


def claim_pending_unit(
    worker: "WorkerHandle", state: ClusterManagerState
) -> WorkUnit | None:
    """Claim the next pending unit of ``state`` for ``worker``, before any
    RPC: concurrent assignment in the same pass cannot double-queue it.
    ``send_claimed_unit`` confirms the claim or gives the unit back."""
    unit = state.next_pending_unit()
    if unit is not None:
        state.mark_frame_as_queued(unit, worker.worker_id, time.time())
    return unit


def claim_is_to_return(
    worker: "WorkerHandle", state: ClusterManagerState, unit: WorkUnit
) -> bool:
    """Whether a claim whose queue-add failed goes back to its pool here.
    Not where the unit is no longer this claim's (its worker was evicted
    meanwhile: the eviction returned its claims, and the unit may be
    another's by now), and not while the worker is silent: a send fails
    there only as the reconnect window ends, and the eviction that is due
    returns everything that is with the worker, under its own cause."""
    record = state.frames.get(unit)
    return (
        record is not None
        and record.worker_id == worker.worker_id
        and not worker.is_silent
    )


async def send_claimed_unit(
    worker: "WorkerHandle",
    job: BlenderJob,
    state: ClusterManagerState,
    unit: WorkUnit,
    *,
    job_id: str | None = None,
    trigger: str | None = None,
) -> bool:
    """RPC a claimed unit onto ``worker``; a unit whose queue-add fails
    goes back to the pending pool. ``trigger``: the kind of the pass that
    claimed it, where that pass does not wait here (master/wakeup.py)."""
    try:
        await worker.queue_frame(job, unit, job_id=job_id, trigger=trigger)
    except Exception as e:  # noqa: BLE001 - worker failure mid-RPC
        logger.warning(
            "Failed to queue unit %s on %08x: %r", unit.label, worker.worker_id, e
        )
        if claim_is_to_return(worker, state, unit):
            state.return_frame_to_pending(unit, "dispatch_failed")
        return False
    return True


async def dispatch_one_pending(
    worker: "WorkerHandle",
    job: BlenderJob,
    state: ClusterManagerState,
    *,
    job_id: str | None = None,
) -> bool:
    """Claim + RPC-dispatch one pending frame of ``state`` onto ``worker``.

    The shared dispatch primitive: every single-job strategy goes through
    here, and the multi-job fair-share loop (sched/manager.py) through its
    two halves (it claims for every worker first and sends to all of them
    at once), so the claim-before-RPC double-queue guard and the
    failure-requeue path have exactly one definition. ``job_id`` is the
    scheduler's submission id, piggybacked on the wire (None on the
    single-job path).
    """
    unit = claim_pending_unit(worker, state)
    if unit is None:
        return False
    return await send_claimed_unit(worker, job, state, unit, job_id=job_id)


async def _queue_one_pending(
    worker: "WorkerHandle", job: BlenderJob, state: ClusterManagerState
) -> bool:
    return await dispatch_one_pending(worker, job, state)


async def naive_fine_strategy(
    job: BlenderJob,
    state: ClusterManagerState,
    workers_fn,
    cancellation: CancellationToken,
    wakeup: DispatchWakeup,
) -> None:
    """One frame at a time per idle worker (reference: strategies.rs:16-68).

    Between passes the loop waits for ``wakeup`` (a finished event that
    left a worker's mirror empty) or for the tick, whichever comes first;
    ``has_empty_queue()`` gates every dispatch as before, so a worker that
    holds a frame never gets a second one."""
    while not cancellation.is_cancelled():
        if state.all_frames_finished():
            return
        check_job_failed(state)
        for worker in workers_fn():
            if worker.is_dead or not worker.has_empty_queue():
                continue
            await _queue_one_pending(worker, job, state)
        await wakeup.wait(NAIVE_FINE_TICK)


async def eager_naive_coarse_strategy(
    job: BlenderJob,
    state: ClusterManagerState,
    workers_fn,
    cancellation: CancellationToken,
    target_queue_size: int,
) -> None:
    """Top every queue up to the target (reference: strategies.rs:70-150)."""
    while not cancellation.is_cancelled():
        if state.all_frames_finished():
            return
        check_job_failed(state)
        for worker in workers_fn():
            if worker.is_dead:
                continue
            deficit = target_queue_size - len(worker.queue)
            for _ in range(max(0, deficit)):
                if not await _queue_one_pending(worker, job, state):
                    break
        await asyncio.sleep(EAGER_COARSE_TICK)


async def dynamic_strategy(
    job: BlenderJob,
    state: ClusterManagerState,
    workers_fn,
    cancellation: CancellationToken,
    options: DynamicStrategyOptions,
) -> None:
    """Target-size top-up with work stealing (reference: strategies.rs:250-405)."""
    while not cancellation.is_cancelled():
        if state.all_frames_finished():
            return
        check_job_failed(state)
        workers = [w for w in workers_fn() if not w.is_dead]
        workers.sort(key=lambda w: len(w.queue))
        for worker in workers:
            if len(worker.queue) >= options.target_queue_size:
                continue
            if await _queue_one_pending(worker, job, state):
                continue
            # Pending pool dry: steal from the busiest worker.
            found = find_busiest_worker_and_frame_to_steal(worker, workers, options)
            if found is None:
                break  # nobody has anything stealable; next tick
            victim, frame = found
            await steal_frame(job, state, worker, victim, frame.unit)
        await asyncio.sleep(DYNAMIC_TICK)


async def steal_frame(
    job: BlenderJob,
    state: ClusterManagerState,
    thief: "WorkerHandle",
    victim: "WorkerHandle",
    unit: WorkUnit | int,
) -> bool:
    """Unqueue from victim, requeue on thief with provenance.

    Tolerates the distributed races exactly like the reference
    (strategies.rs:340-396): if the victim already started rendering or
    finished the unit, the steal silently aborts.
    """
    if isinstance(unit, int):
        unit = WorkUnit(unit)
    try:
        result = await victim.unqueue_frame(job.job_name, unit)
    except Exception as e:  # noqa: BLE001
        logger.warning("Steal unqueue RPC failed on %08x: %s", victim.worker_id, e)
        return False
    if result in (
        pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_RENDERING,
        pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_FINISHED,
    ):
        return False
    if result != pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED:
        logger.warning("Steal unqueue errored on %08x: %s", victim.worker_id, result)
        return False
    # The victim can be marked dead between steal selection and here (the
    # unqueue RPC is an await point — heartbeat eviction interleaves).
    # Three cases, each leaving the frame pending-or-owned EXACTLY once:
    # - eviction already requeued it (record no longer points at the
    #   victim): do nothing — requeueing on the thief as well would put
    #   the frame in play twice;
    # - the victim died but eviction can no longer see the frame (the
    #   unqueue above removed it from the mirror eviction sweeps): requeue
    #   it HERE or it would be lost forever;
    # - victim alive and still owning the record: proceed with the steal.
    record = state.frames.get(unit)
    owned_by_victim = (
        record is not None
        and record.status is FrameStatus.QUEUED_ON_WORKER
        and record.worker_id == victim.worker_id
    )
    if victim.is_dead or not owned_by_victim:
        if owned_by_victim:
            state.return_frame_to_pending(unit, "eviction")
        logger.warning(
            "Steal of unit %s aborted: victim %08x %s mid-steal.",
            unit.label,
            victim.worker_id,
            "died" if victim.is_dead else "lost the assignment",
        )
        return False
    victim.frames_stolen_count += 1
    try:
        await thief.queue_frame(job, unit, stolen_from=victim.worker_id)
    except Exception as e:  # noqa: BLE001
        logger.warning("Steal requeue failed on %08x: %s", thief.worker_id, e)
        state.return_frame_to_pending(unit, "steal")
        return False
    logger.debug(
        "Stole unit %s: %08x -> %08x", unit.label, victim.worker_id, thief.worker_id
    )
    return True


async def preempt_frame(
    job: BlenderJob,
    state: ClusterManagerState,
    victim: "WorkerHandle",
    unit: WorkUnit | int,
) -> bool:
    """Unqueue a not-yet-rendering frame back to its job's pending pool.

    The fair-share scheduler's preemption primitive: the first half of a
    steal (the same frame-queue-remove RPC with the same race tolerance —
    ``already-rendering`` / ``already-finished`` silently abort), except
    the frame returns to ITS OWN job's pending pool instead of moving to a
    thief, freeing the worker slot for an under-share job's next dispatch.
    """
    if isinstance(unit, int):
        unit = WorkUnit(unit)
    try:
        result = await victim.unqueue_frame(job.job_name, unit)
    except Exception as e:  # noqa: BLE001
        logger.warning(
            "Preempt unqueue RPC failed on %08x: %s", victim.worker_id, e
        )
        return False
    if result != pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED:
        return False
    # Same await-point races as steal_frame: the victim may have died (or
    # the assignment moved) while the RPC was in flight. Requeue the unit
    # here exactly when this worker still owns its live assignment —
    # eviction already requeued it otherwise.
    record = state.frames.get(unit)
    owned_by_victim = (
        record is not None
        and record.status is FrameStatus.QUEUED_ON_WORKER
        and record.worker_id == victim.worker_id
    )
    if not owned_by_victim:
        logger.warning(
            "Preemption of unit %s aborted: victim %08x lost the "
            "assignment mid-RPC.",
            unit.label,
            victim.worker_id,
        )
        return False
    state.return_frame_to_pending(unit, "preemption")
    return True


async def run_strategy(
    job: BlenderJob,
    state: ClusterManagerState,
    workers_fn,
    cancellation: CancellationToken,
    *,
    cost_service=None,
    wakeup: DispatchWakeup,
) -> None:
    """Dispatch on the job's strategy (reference: master/src/cluster/mod.rs:622-654).

    ``cost_service`` is the master's shared predictive cost model
    (sched/cost_model.CostModelService); the tpu-batch strategy prices
    its auction off it (warm-started from ``TRC_COST_MODEL`` snapshots
    and shared with the speculation loop). The reference strategies
    ignore it — their dispatch order is fixed by contract. ``wakeup`` is
    the manager's dispatch wake-up; naive-fine alone waits on it (the
    other loops keep queues no finished event can run shallow, and their
    ticks).
    """
    strategy = job.frame_distribution_strategy
    if strategy.strategy_type == "naive-fine":
        await naive_fine_strategy(job, state, workers_fn, cancellation, wakeup)
    elif strategy.strategy_type == "eager-naive-coarse":
        await eager_naive_coarse_strategy(
            job, state, workers_fn, cancellation, strategy.eager.target_queue_size
        )
    elif strategy.strategy_type == "dynamic":
        await dynamic_strategy(job, state, workers_fn, cancellation, strategy.dynamic)
    elif strategy.strategy_type == "tpu-batch":
        from tpu_render_cluster.master.tpu_batch import tpu_batch_strategy

        await tpu_batch_strategy(
            job,
            state,
            workers_fn,
            cancellation,
            strategy.tpu_batch,
            cost_service=cost_service,
        )
    else:
        raise ValueError(f"Unknown strategy: {strategy.strategy_type}")
