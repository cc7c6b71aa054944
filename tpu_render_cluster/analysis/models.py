"""Analysis-side trace models (reference: analysis/core/models.py).

Loads the raw-trace JSON written by the master and exposes the derived
quantities the metric modules need. Validates the same invariants as the
reference loader: well-formed JSON, and worker count equal to the job's
``wait_for_number_of_workers`` (analysis/core/models.py:278-282).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.traces.worker_trace import WorkerTrace


@dataclass(frozen=True)
class JobTrace:
    job: BlenderJob
    job_started_at: float
    job_finished_at: float
    worker_traces: dict[str, WorkerTrace]

    @classmethod
    def load_from_trace_file(cls, trace_file_path: str | Path) -> "JobTrace":
        path = Path(trace_file_path)
        if not path.is_file():
            raise RuntimeError(f"Missing raw trace file: {path}!")
        data = json.loads(path.read_text(encoding="utf-8"))
        job = BlenderJob.from_dict(data["job"])
        master = data["master_trace"]
        worker_traces = {
            name: WorkerTrace.from_dict(raw)
            for name, raw in data["worker_traces"].items()
        }
        if len(worker_traces) != job.wait_for_number_of_workers:
            raise ValueError(
                f"Invalid data: len(worker_traces) = {len(worker_traces)}, but "
                f"wait_for_number_of_workers = {job.wait_for_number_of_workers}!"
            )
        return cls(
            job=job,
            job_started_at=float(master["job_start_time"]),
            job_finished_at=float(master["job_finish_time"]),
            worker_traces=worker_traces,
        )

    # -- derived quantities (reference: analysis/core/models.py:133-313) ----

    def job_duration(self) -> float:
        return self.job_finished_at - self.job_started_at

    def cluster_size(self) -> int:
        return self.job.wait_for_number_of_workers

    def strategy_type(self) -> str:
        return self.job.frame_distribution_strategy.strategy_type

    def get_last_frame_finished_at(self) -> float:
        return max(
            last_frame_finished_at(trace) for trace in self.worker_traces.values()
        )


def last_frame_finished_at(trace: WorkerTrace) -> float:
    if not trace.frame_render_traces:
        return trace.job_start_time
    return max(t.details.exited_process_at for t in trace.frame_render_traces)


def worker_tail_delay(trace: WorkerTrace, global_last_finish: float) -> float:
    """Gap between the global last frame finish and this worker's last frame
    finish (reference: analysis/core/models.py:175-181 'without teardown')."""
    return max(0.0, global_last_finish - last_frame_finished_at(trace))


def worker_active_time(trace: WorkerTrace) -> float:
    """Total wall time spent inside frame renders: the union of the
    frames' intervals. A worker saves frame i while it renders frame i+1
    (worker/queue.py), so consecutive frames overlap and their plain sum
    would count that time twice (a utilization over 1); on a serial trace
    the union IS the sum."""
    active, covered_until = 0.0, float("-inf")
    for start, end in sorted(
        (t.details.started_process_at, t.details.exited_process_at)
        for t in trace.frame_render_traces
    ):
        if end < start:
            raise ValueError("Total execution time is negative?!")
        active += max(0.0, end - max(start, covered_until))
        covered_until = max(covered_until, end)
    return active


def mean_frame_time(trace: WorkerTrace) -> float:
    """Mean duration of a frame, first point to last (overlap and all)."""
    frames = trace.frame_render_traces
    if not frames:
        return 0.0
    return sum(t.details.total_execution_time() for t in frames) / len(frames)
