"""Scheduler-facing job models: submissions and their lifecycle records.

A ``JobSpec`` is what a client submits: the reference ``BlenderJob`` TOML
payload plus the two scheduling knobs the reference never had — a
``weight`` (the job's fair share of in-flight frame slots relative to its
priority-class peers) and an integer ``priority`` class (strictly higher
classes are served first; weighted fair-share applies WITHIN a class).

A ``JobRun`` is the master-side lifecycle record of one submission:
``queued -> running -> finished | cancelled``, with the per-job frame
table (``ClusterManagerState``) attached at admission, plus the
time-weighted share accounting the acceptance criteria (achieved vs.
target share over the multi-job overlap window) and the ``sched`` section
of ``statistics.json`` are computed from.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.master.state import ClusterManagerState

JOB_QUEUED = "queued"
JOB_RUNNING = "running"
JOB_FINISHED = "finished"
JOB_CANCELLED = "cancelled"

JOB_STATES = (JOB_QUEUED, JOB_RUNNING, JOB_FINISHED, JOB_CANCELLED)


@dataclass(frozen=True)
class JobSpec:
    """One submission: the job payload + its scheduling parameters."""

    job: BlenderJob
    weight: float = 1.0
    priority: int = 0

    def __post_init__(self) -> None:
        if not math.isfinite(self.weight) or self.weight <= 0.0:
            raise ValueError(f"weight must be a positive finite number, got {self.weight!r}")
        if not isinstance(self.priority, int):
            raise ValueError(f"priority must be an integer, got {self.priority!r}")

    def to_dict(self) -> dict[str, Any]:
        return {
            "job": self.job.to_dict(),
            "weight": self.weight,
            "priority": self.priority,
        }

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "JobSpec":
        if "job" not in data:
            raise ValueError("job spec must carry a 'job' object")
        return cls(
            job=BlenderJob.from_dict(data["job"]),
            weight=float(data.get("weight", 1.0)),
            priority=int(data.get("priority", 0)),
        )


@dataclass
class JobRun:
    """Lifecycle record of one submission on the scheduler."""

    job_id: str
    spec: JobSpec
    submitted_at: float
    status: str = JOB_QUEUED
    admitted_at: float | None = None
    finished_at: float | None = None
    # Per-job frame table; attached at admission, kept after the job ends
    # (frozen) so late worker events resolve to "defunct" instead of
    # aliasing a newer job.
    state: ClusterManagerState | None = None
    preemptions: int = 0
    # Time-weighted share accounting over the MULTI-JOB OVERLAP window
    # (ticks during which >= 2 jobs were running): integrals of this job's
    # in-flight count, the cluster-wide in-flight total, and this job's
    # target share, plus the window's length. Achieved share is
    # in_flight integral / total integral; target share is its integral
    # over the window length.
    overlap_in_flight_integral: float = 0.0
    overlap_total_integral: float = 0.0
    overlap_target_integral: float = 0.0
    overlap_seconds: float = 0.0
    last_target_share: float = 0.0
    # The job's announcement to the pool: when it was sent to each worker
    # that prepares announced jobs (worker_id -> wall time), when each of
    # them reported the job ready, and whether its ``job_announce`` span
    # has been written (every announced worker ready, or the job ended).
    announced_at: dict[int, float] = field(default_factory=dict)
    ready_at: dict[int, float] = field(default_factory=dict)
    announce_closed: bool = False
    # The workers the job's announcement was handed to the sender of (a
    # worker silent at the admission is told at its reconnect).
    announce_sent: set[int] = field(default_factory=set)
    # Why the job waits, while it does: ``worker_barrier: wants N, M live``
    # or ``active_slot`` (queued), ``silent_worker`` (running, nothing
    # pending and every unit in flight with a silent worker); else None.
    waiting_on: str | None = None
    # An ended job's view as it ended: it never changes again, and a
    # long-lived service answers ``status`` from it.
    final_view: dict[str, Any] | None = None
    extras: dict[str, Any] = field(default_factory=dict)

    @property
    def job_name(self) -> str:
        return self.spec.job.job_name

    def is_active(self) -> bool:
        return self.status in (JOB_QUEUED, JOB_RUNNING)

    def admission_wait_seconds(self) -> float | None:
        if self.admitted_at is None:
            return None
        return self.admitted_at - self.submitted_at

    def makespan_seconds(self) -> float | None:
        """Admission to completion (None until the job ends, and for
        cancelled jobs that never ran)."""
        if self.admitted_at is None or self.finished_at is None:
            return None
        return self.finished_at - self.admitted_at

    def achieved_share(self) -> float | None:
        """This job's realized fraction of in-flight slots over the
        overlap window (None when the job never overlapped another)."""
        if self.overlap_total_integral <= 0.0:
            return None
        return self.overlap_in_flight_integral / self.overlap_total_integral

    def target_share(self) -> float | None:
        """Mean fair-share target over the same overlap window."""
        if self.overlap_seconds <= 0.0:
            return None
        return self.overlap_target_integral / self.overlap_seconds

    def view(self) -> dict[str, Any]:
        """Live JSON view (cluster_view 'jobs' section / control 'status')."""
        from tpu_render_cluster.master.cluster import job_state_view

        if self.final_view is not None:
            return self.final_view
        out: dict[str, Any] = {
            "job_id": self.job_id,
            "job_name": self.job_name,
            "weight": self.spec.weight,
            "priority": self.spec.priority,
            "status": self.status,
            "submitted_at": self.submitted_at,
            "admitted_at": self.admitted_at,
            "finished_at": self.finished_at,
            "admission_wait_seconds": self.admission_wait_seconds(),
            "makespan_seconds": self.makespan_seconds(),
            "preemptions": self.preemptions,
            "waiting_on": self.waiting_on,
            "share": {
                "target": self.target_share(),
                "achieved": self.achieved_share(),
                "overlap_seconds": self.overlap_seconds,
                "last_target": self.last_target_share,
            },
        }
        if self.state is not None:
            out.update(job_state_view(self.state))
        else:
            out.update(
                {
                    "frames_total": 0,
                    "frames_finished": 0,
                    "frames_pending": 0,
                    "frames_in_flight": 0,
                }
            )
        return out
