"""Per-worker performance reducer.

Folds a ``WorkerTrace`` into totals, matching the reference's metric contract
exactly (reference: shared/src/results/performance.rs:12-144), including its
idle-time definition: lead-in before the first frame, tail after the last
frame, and gaps between consecutive middle frames. Note the reference's
branch ordering means the last frame's gap to its predecessor is *not*
counted — we replicate that deliberately since processed-results numbers are
part of the metric contract. Durations serialise as fractional seconds
(``DurationSecondsWithFrac<f64>`` equivalence).

One extension: a worker whose queue saves frame *i* while it renders frame
*i+1* (worker/queue.py) hands in frames whose intervals overlap. Idle is
the time NO frame covers: a gap counts from the latest exit of any earlier
frame, and a frame that starts under an earlier one adds none. On a serial
trace, where each frame starts after the one before has exited, every
number is the reference's.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from tpu_render_cluster.traces.worker_trace import WorkerTrace


def _nonnegative(value: float, what: str) -> float:
    if value < 0:
        raise ValueError(f"{what} is negative ({value} s).")
    return value


@dataclass(frozen=True)
class WorkerPerformance:
    total_frames_rendered: int
    total_frames_queued: int
    total_frames_stolen_from_queue: int
    total_times_reconnected: int
    total_time: float
    total_blend_file_reading_time: float
    total_rendering_time: float
    total_image_saving_time: float
    total_idle_time: float

    @classmethod
    def from_worker_trace(cls, trace: WorkerTrace) -> "WorkerPerformance":
        total_time = _nonnegative(
            trace.job_finish_time - trace.job_start_time, "Total job duration"
        )

        reading = 0.0
        rendering = 0.0
        saving = 0.0
        idle = 0.0

        frames = trace.frame_render_traces
        covered_until = trace.job_start_time  # latest exit of the frames so far
        for i, frame in enumerate(frames):
            d = frame.details
            reading += _nonnegative(
                d.finished_loading_at - d.started_process_at, "File reading duration"
            )
            rendering += _nonnegative(
                d.finished_rendering_at - d.started_rendering_at, "Rendering duration"
            )
            saving += _nonnegative(
                d.file_saving_finished_at - d.file_saving_started_at, "File saving duration"
            )
            if i == 0:
                idle += _nonnegative(
                    d.started_process_at - trace.job_start_time,
                    "Idle time before first frame",
                )
            elif i == len(frames) - 1:
                idle += _nonnegative(
                    trace.job_finish_time - max(covered_until, d.exited_process_at),
                    "Idle time after last frame",
                )
            else:
                idle += max(0.0, d.started_process_at - covered_until)
            covered_until = max(covered_until, d.exited_process_at)

        return cls(
            total_frames_rendered=len(frames),
            total_frames_queued=trace.total_queued_frames,
            total_frames_stolen_from_queue=trace.total_queued_frames_removed_from_queue,
            total_times_reconnected=len(trace.reconnection_traces),
            total_time=total_time,
            total_blend_file_reading_time=reading,
            total_rendering_time=rendering,
            total_image_saving_time=saving,
            total_idle_time=idle,
        )

    def to_dict(self) -> dict[str, Any]:
        return {
            "total_frames_rendered": self.total_frames_rendered,
            "total_frames_queued": self.total_frames_queued,
            "total_frames_stolen_from_queue": self.total_frames_stolen_from_queue,
            "total_times_reconnected": self.total_times_reconnected,
            "total_time": self.total_time,
            "total_blend_file_reading_time": self.total_blend_file_reading_time,
            "total_rendering_time": self.total_rendering_time,
            "total_image_saving_time": self.total_image_saving_time,
            "total_idle_time": self.total_idle_time,
        }
