"""Pure fair-share arithmetic: slot targets, dispatch picks, preemption.

All functions are side-effect-free over plain inputs so the scheduling
policy is unit-testable without a cluster (the same design rule as the
steal-candidate selectors in master/strategies.py).

Model: the cluster offers ``total_slots`` in-flight frame slots (live
workers x per-worker target queue size). Jobs are split into strict
priority classes (higher ``priority`` first); within a class each job's
target is its weight-proportional share of the slots the class received,
capped by the job's *demand* (it can never use more slots than it has
frames left), with the leftover water-filling down to lower classes.

Dispatch follows the classic weighted-fair-queueing rule — serve the
runnable job with the smallest normalized load ``load / weight``, where
load is the job's in-flight work in PREDICTED SECONDS when the cost model
(sched/cost_model.py) has priced the inputs and the in-flight unit count
before any history exists — which converges to the weight-proportional
allocation without ever needing the target values; the targets exist for
preemption decisions and observability (``sched_job_share`` gauges, the
acceptance criterion's achieved-vs-target comparison). Targets and
preemption stay in SLOT units: slots are what the pool physically offers
(worker queue positions), and a seconds-denominated target would preempt
a job for merely holding slow units it cannot help holding.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

# One whole slot of slack before anybody preempts: fractional targets
# (e.g. 4.5 vs 1.5 on 6 slots) must not cause steady-state thrash.
PREEMPTION_SLACK_SLOTS = 1.0
_EPS = 1e-9


@dataclass(frozen=True)
class JobShareInput:
    """One running job's instantaneous scheduling inputs.

    ``in_flight_cost`` is the job's in-flight work in PREDICTED SECONDS
    (the cost model's per-unit predictions summed over its queued +
    rendering units). When present, the WFQ dispatch pick meters load by
    it instead of the unit count, so a job holding one predicted-slow
    unit is not treated as lighter than a job holding three fast ones.
    Callers must supply it uniformly across one tick's inputs (all jobs
    or none) — mixing seconds with counts would compare incommensurable
    loads; ``pick_job_to_dispatch`` falls back to the count for any job
    missing it.
    """

    job_id: str
    weight: float
    priority: int
    in_flight: int
    pending: int
    in_flight_cost: float | None = None

    @property
    def demand(self) -> int:
        """Max slots this job can usefully hold right now."""
        return self.in_flight + self.pending

    @property
    def load(self) -> float:
        """The WFQ load measure: predicted seconds when known, else units."""
        return (
            self.in_flight_cost
            if self.in_flight_cost is not None
            else float(self.in_flight)
        )


def compute_slot_targets(
    jobs: Sequence[JobShareInput], total_slots: float
) -> dict[str, float]:
    """Per-job target in-flight slots (fractional).

    Strict priority: classes are served highest-first, each consuming up
    to its total demand. Within a class, weighted water-filling: each job
    gets its weight-proportional share of the class's slots, demand-capped
    jobs are clamped and their surplus redistributed among the rest.
    """
    targets = {job.job_id: 0.0 for job in jobs}
    remaining = max(0.0, float(total_slots))
    for priority in sorted({job.priority for job in jobs}, reverse=True):
        if remaining <= _EPS:
            break
        unsatisfied = {
            job.job_id: job
            for job in jobs
            if job.priority == priority and job.demand > 0
        }
        while unsatisfied and remaining > _EPS:
            total_weight = sum(job.weight for job in unsatisfied.values())
            clamped_id = None
            for job_id, job in unsatisfied.items():
                grant = remaining * job.weight / total_weight
                if job.demand <= grant + _EPS:
                    clamped_id = job_id
                    break
            if clamped_id is None:
                # Nobody is demand-capped: the proportional split stands.
                for job_id, job in unsatisfied.items():
                    targets[job_id] = remaining * job.weight / total_weight
                remaining = 0.0
                break
            job = unsatisfied.pop(clamped_id)
            targets[clamped_id] = float(job.demand)
            remaining -= job.demand
    return targets


def pick_job_to_dispatch(
    jobs: Sequence[JobShareInput],
) -> str | None:
    """The job the next free slot should serve, or None when nothing is
    runnable (no pending frames anywhere).

    Highest priority class with pending work wins outright; within it,
    the weighted-fair-queueing pick: minimal ``load / weight`` — load in
    predicted seconds when the cost model priced the inputs
    (``in_flight_cost``), else the in-flight unit count — ties broken by
    input order (submit order, so the allocation is deterministic).
    """
    runnable = [job for job in jobs if job.pending > 0]
    if not runnable:
        return None
    top = max(job.priority for job in runnable)
    best: JobShareInput | None = None
    for job in runnable:
        if job.priority != top:
            continue
        if best is None or job.load / job.weight < best.load / best.weight - _EPS:
            best = job
    assert best is not None
    return best.job_id


def pick_preemption(
    jobs: Sequence[JobShareInput],
    targets: dict[str, float],
) -> tuple[str, str] | None:
    """(over-share job, starved job) when preempting one slot is justified.

    A job is *starved* when it has pending frames and sits at least one
    whole slot under its target; a job is *over* when it holds at least
    ``PREEMPTION_SLACK_SLOTS`` more than its target. Both must exist
    simultaneously — otherwise natural completion drains the imbalance
    and preempting would only waste a queued frame's wait time. The most
    over and the most starved are paired (one preemption per call; the
    caller rate-limits per tick).
    """
    starved: JobShareInput | None = None
    over: JobShareInput | None = None
    for job in jobs:
        target = targets.get(job.job_id, 0.0)
        deficit = target - job.in_flight
        surplus = job.in_flight - target
        if job.pending > 0 and deficit >= 1.0 - _EPS:
            if starved is None or deficit > targets.get(starved.job_id, 0.0) - starved.in_flight:
                starved = job
        if surplus >= PREEMPTION_SLACK_SLOTS - _EPS:
            if over is None or surplus > over.in_flight - targets.get(over.job_id, 0.0):
                over = job
    if starved is None or over is None or starved.job_id == over.job_id:
        return None
    return over.job_id, starved.job_id

