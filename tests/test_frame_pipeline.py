"""The two-stage frame: a frame's save runs beside the next frames' render
and beside the saves of the frames ahead of it, up to `SAVE_FRAMES` at
once, and two frames are on the device at a time where the backend can
issue a frame's device work without waiting for it.

A fake two-stage backend (sleeps on threads, a record of what happened
when), alone and with an issue / collect pair over a fake device that runs
one frame at a time in the order it was handed them, drives
`WorkerAutomaticQueue` through the cases where timing matters; the
tpu-raytrace backend on small CPU frames shows that the
files, the steps and the series are what a serial frame's were; the
reducers (`WorkerPerformance.from_worker_trace`, the analysis suite's
utilization) are held to "idle is the time no frame covers" on an
overlapped trace and to their old numbers on a serial one; and one job
goes through `master run-job` and a `tpu-raytrace` worker process to its
processed results.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import json
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.obs import FRAME_STEPS, MetricsRegistry, Tracer, validate_trace_file
from tpu_render_cluster.obs.prometheus import render_prometheus
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.traces.performance import WorkerPerformance
from tpu_render_cluster.traces.worker_trace import (
    FrameRenderTime,
    WorkerFrameTrace,
    WorkerTrace,
    WorkerTraceBuilder,
)
from tpu_render_cluster.utils.cancellation import CancellationToken
from tpu_render_cluster.worker.backends.base import IssuedFrame, RenderBackend, RenderedFrame
from tpu_render_cluster.worker.backends.mock import MockBackend
from tpu_render_cluster.worker.queue import (
    DEVICE_FRAMES,
    LOOP_STATES,
    SAVE_FRAMES,
    SAVE_TRACKS,
    FrameState,
    WorkerAutomaticQueue,
)

from tests.test_steps import loop_clock


def make_job(name: str, frames: int, output: str = "%BASE%/out", file_format: str = "JPEG") -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description=None,
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=1,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path=output,
        output_file_name_format="rendered-#####",
        output_file_format=file_format,
    )


class RecordingSender:
    """Keeps what was sent, with the time and whether the frame's file was
    there at that moment."""

    def __init__(self, directory: Path | None = None) -> None:
        self.sent: list[tuple[float, object, bool]] = []
        self.directory = directory

    async def send_message(self, message) -> None:
        there = (
            self.directory is not None
            and isinstance(message, pm.WorkerFrameQueueItemFinishedEvent)
            and (self.directory / f"{message.frame_index}.bin").exists()
        )
        self.sent.append((time.time(), message, there))

    def finished(self) -> list[pm.WorkerFrameQueueItemFinishedEvent]:
        return [m for _, m, _ in self.sent if isinstance(m, pm.WorkerFrameQueueItemFinishedEvent)]


class TwoStageBackend(RenderBackend):
    """A device stage and a save stage that sleep, each on the thread it is
    given, and say when they began and ended: `log` holds
    `(what, frame, wall time)` with `what` one of device_start, dispatched,
    device_end, save_start, save_end."""

    def __init__(
        self, directory: Path, *, dispatch_seconds: float = 0.005, device_seconds: float = 0.04,
        save_seconds: float = 0.02, fail_saves: frozenset[int] = frozenset(),
        fail_devices: frozenset[int] = frozenset(), slow_saves: dict[int, float] | None = None,
    ) -> None:
        self.directory = directory
        self.dispatch_seconds = dispatch_seconds
        self.device_seconds = device_seconds
        self.save_seconds = save_seconds
        self.slow_saves = slow_saves or {}  # frame -> its own save's seconds
        self.fail_saves = fail_saves
        self.fail_devices = fail_devices
        self.log: list[tuple[str, int, float]] = []
        self._lock = threading.Lock()
        self.save_threads: set[str] = set()

    def note(self, what: str, frame: int) -> float:
        now = time.time()
        with self._lock:
            self.log.append((what, frame, now))
        return now

    def times(self, what: str) -> dict[int, float]:
        with self._lock:
            return {frame: at for name, frame, at in self.log if name == what}

    def most_at_once(self, stage: str) -> int:
        """The most frames that were in that stage (`device`: begun, or issued, and not yet
        collected; `save`) at once."""
        with self._lock:
            log = sorted(self.log, key=lambda entry: entry[2])
        most = now = 0
        for what, _frame, _at in log:
            now += {f"{stage}_start": 1, f"{stage}_end": -1}.get(what, 0)
            most = max(most, now)
        return most

    def on_device(self) -> int:
        return self.most_at_once("device")

    async def render_frame(self, job, frame_index, tile=None):
        rendered = await self.render_device_stage(job, frame_index, tile, dispatched=lambda: None)
        return await asyncio.to_thread(rendered.save)

    async def render_device_stage(self, job, frame_index, tile=None, *, dispatched):
        return await asyncio.to_thread(self._device, frame_index, dispatched)

    def _device(self, frame: int, dispatched) -> RenderedFrame:
        started = self.note("device_start", frame)
        if frame in self.fail_devices:
            raise RuntimeError(f"device stage of frame {frame} failed")
        time.sleep(self.dispatch_seconds)
        self.note("dispatched", frame)
        dispatched()
        time.sleep(self.device_seconds)
        ended = self.note("device_end", frame)
        return RenderedFrame(save=functools.partial(self._save, frame, started, ended))

    def _save(self, frame: int, started: float, rendered: float) -> FrameRenderTime:
        self.save_threads.add(threading.current_thread().name)
        save_started = self.note("save_start", frame)
        time.sleep(self.slow_saves.get(frame, self.save_seconds))
        if frame in self.fail_saves:
            self.note("save_end", frame)
            raise OSError(f"disk full under frame {frame}")
        self.directory.mkdir(parents=True, exist_ok=True)
        (self.directory / f"{frame}.bin").write_bytes(b"pixels")
        save_ended = self.note("save_end", frame)
        return FrameRenderTime(
            started_process_at=started,
            finished_loading_at=started,
            started_rendering_at=started,
            finished_rendering_at=rendered,
            file_saving_started_at=save_started,
            file_saving_finished_at=save_ended,
            exited_process_at=time.time(),
        )


class IssueAheadBackend(TwoStageBackend):
    """The same two stages with the device stage parted: `issue` takes
    `dispatch_seconds` of host time and hands the frame to a fake device,
    which runs one frame at a time, `device_seconds` each, in the order it
    was handed them; `collect` blocks until the device has finished the
    frame. In `log`, `device_start` is where the issue began, `dispatched`
    where it returned, `collect_start` / `device_end` the wait's two ends.
    With `ends_behind_next_dispatch=n` the fake device ends none of the
    frames before the `n`-th until the frame behind it has been dispatched
    (it gives up after 20 s): what a test then reads is whether the loop
    issues a frame while the wait ahead of it is under way, and not how
    soon a loaded machine gives the loop its turn."""

    def __init__(
        self, directory: Path, *, fail_collects: frozenset[int] = frozenset(),
        ends_behind_next_dispatch: int = 0, **stages,
    ) -> None:
        super().__init__(directory, **stages)
        self.fail_collects = fail_collects
        self.ends_behind_next_dispatch = ends_behind_next_dispatch
        self._device_free_at = 0.0

    def issue_device_stage(self, job, frame_index, tile=None) -> IssuedFrame:
        started = self.note("device_start", frame_index)
        if frame_index in self.fail_devices:
            raise RuntimeError(f"issue of frame {frame_index} failed")
        time.sleep(self.dispatch_seconds)
        issued = self.note("dispatched", frame_index)
        with self._lock:
            done_at = self._device_free_at = max(self._device_free_at, issued) + self.device_seconds
        return IssuedFrame(collect=functools.partial(self._collect, frame_index, started, done_at))

    def _collect(self, frame: int, started: float, done_at: float) -> RenderedFrame:
        self.note("collect_start", frame)
        time.sleep(max(0.0, done_at - time.time()))
        give_up_at = time.time() + 20.0
        while frame < self.ends_behind_next_dispatch and frame + 1 not in self.times("dispatched"):
            if time.time() > give_up_at:
                break
            time.sleep(0.001)
        ended = self.note("device_end", frame)
        if frame in self.fail_collects:
            raise RuntimeError(f"collect of frame {frame} failed")
        return RenderedFrame(save=functools.partial(self._save, frame, started, ended))

BACKENDS = {"one on the device": TwoStageBackend, "two on the device": IssueAheadBackend}
both_backends = pytest.mark.parametrize("make_backend", BACKENDS.values(), ids=BACKENDS.keys())


@dataclasses.dataclass
class Driven:
    queue: WorkerAutomaticQueue
    sender: RecordingSender
    traces: WorkerTraceBuilder
    metrics: MetricsRegistry
    tracer: Tracer
    wall: float = 0.0  # the loop's wall time by its own clock (tests/test_steps.py::loop_clock)

    def counter(self, name: str, **labels) -> float:
        return self.metrics.counter(name, labels=tuple(labels)).value(**labels)


def drive(backend, body, *, directory: Path | None = None) -> Driven:
    """Run `body(driven)` (a coroutine function) against a started queue."""
    driven = Driven(
        queue=None, sender=RecordingSender(directory), traces=WorkerTraceBuilder(),
        metrics=MetricsRegistry(), tracer=Tracer("worker-pipeline-test"),
    )

    async def run() -> None:
        driven.queue = WorkerAutomaticQueue(
            backend, driven.sender, driven.traces, CancellationToken(),
            metrics=driven.metrics, span_tracer=driven.tracer,
        )
        edges = loop_clock(driven.queue)
        driven.queue.start()
        try:
            await asyncio.wait_for(body(driven), 150.0)
        finally:
            await driven.queue.join()
        driven.wall = edges[-1] - edges[0]

    asyncio.run(run())
    return driven


async def until(condition, seconds: float = 10.0) -> None:
    deadline = time.perf_counter() + seconds
    while not condition():
        assert time.perf_counter() < deadline, "timed out"
        await asyncio.sleep(0.002)


def render_all(backend, frames: int, *, directory: Path | None = None, job=None) -> Driven:
    job = job or make_job("two-stage", frames)

    async def body(driven: Driven) -> None:
        for frame in range(1, frames + 1):
            driven.queue.queue_frame(job, frame)
        await until(lambda: len(driven.sender.finished()) == frames)

    return drive(backend, body, directory=directory)


def most_rendering(driven: Driven) -> int:
    """The most units that were RENDERING at once: a unit is from its rendering event to its finished event."""
    rendering = most = 0
    for _, message, _ in driven.sender.sent:
        rendering += 1 if isinstance(message, pm.WorkerFrameQueueItemRenderingEvent) else -1
        most = max(most, rendering)
    return most


# -- the pipeline, on a fake backend ------------------------------------------------


def test_the_next_device_stage_starts_before_the_save_ends_and_behind_its_dispatch(tmp_path):
    backend = TwoStageBackend(tmp_path)
    driven = render_all(backend, 5)
    device_start, dispatched = backend.times("device_start"), backend.times("dispatched")
    save_start, save_end = backend.times("save_start"), backend.times("save_end")
    for frame in range(1, 5):
        # beside: the next frame's device stage is open before this save ends
        assert device_start[frame + 1] < save_end[frame]
        # behind: and its device work was issued before this save began
        assert dispatched[frame + 1] <= save_start[frame]
    # the last frame has nothing to wait behind
    assert save_start[5] - backend.times("device_end")[5] < 0.25
    assert driven.counter("worker_frames_saved_beside_render_total") == 4
    assert driven.counter("worker_frames_issued_ahead_total") == 0  # it cannot part issue from collect
    assert backend.save_threads == {"frame-save_0"}  # one thread, not the default executor's many


def test_a_frame_is_issued_before_the_wait_ahead_of_it_returns_and_behind_nothing(tmp_path):
    """The order of the events, whatever the machine's load: the fake device ends a frame only
    once the frame behind it has been dispatched (a loop that waited for the collect first
    would hang it for 20 s and fail the order below), so no assertion here reads how soon the
    loop got its turn. Until PR 53 the device ended a frame 40 ms after its dispatch, and on
    the driver's machine under `-n 6` the loop's turn came later than that:
    `dispatched[frame] < device_end[frame - 1]` tripped (reproduced with twelve spinning
    processes on eight cores)."""
    backend = IssueAheadBackend(tmp_path, ends_behind_next_dispatch=6)
    driven = render_all(backend, 6)
    device_start, dispatched = backend.times("device_start"), backend.times("dispatched")
    device_end, save_start, save_end = (backend.times(what) for what in ("device_end", "save_start", "save_end"))
    # the second frame is on the device before the first frame's wait returns
    assert dispatched[2] < device_end[1]
    for frame in range(3, 7):
        # the frame two ahead has been collected (never three on the device) ...
        assert device_start[frame] >= device_end[frame - 2]
        # ... and the wait for the frame ahead is still under way: the device finds this
        # frame in its queue the moment that one ends
        assert dispatched[frame] < device_end[frame - 1]
        # behind nothing: the save of the frame that made room begins behind THIS dispatch
        # (PR 45's rule), the issue does not wait for that save
        assert dispatched[frame] <= save_start[frame - 2]
        assert device_start[frame] < save_end[frame - 2]
    # with nothing left to issue the last two saves wait behind nothing: they began at all
    assert {5, 6} <= set(save_end)
    assert backend.on_device() == DEVICE_FRAMES == 2
    assert driven.counter("worker_frames_issued_ahead_total") == 5  # n - 1 of n back to back
    assert driven.counter("worker_frames_saved_beside_render_total") == 5
    assert [event.frame_index for event in driven.sender.finished()] == [1, 2, 3, 4, 5, 6]


@both_backends
def test_finished_events_leave_in_frame_order_and_each_after_its_file(tmp_path, make_backend):
    backend = make_backend(tmp_path, device_seconds=0.02, save_seconds=0.03)
    driven = render_all(backend, 6, directory=tmp_path)
    finished = driven.sender.finished()
    assert [event.frame_index for event in finished] == [1, 2, 3, 4, 5, 6]
    assert all(event.result == pm.FRAME_QUEUE_ITEM_FINISHED_OK for event in finished)
    sent_at = {
        message.frame_index: (at, there) for at, message, there in driven.sender.sent
        if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent)
    }
    save_end = backend.times("save_end")
    for frame, (at, there) in sent_at.items():
        assert there, f"finished event of frame {frame} left before its file was in place"
        assert at >= save_end[frame]
    # the rendering event of i+1 may precede the finished event of i, and here it does; with
    # two frames on the device and one saving, so does the rendering event of i+2
    order = [
        (type(message).__name__, message.frame_index) for _, message, _ in driven.sender.sent
    ]
    ahead = 2 if make_backend is IssueAheadBackend else 1
    assert order.index(("WorkerFrameQueueItemRenderingEvent", 1 + ahead)) < order.index(
        ("WorkerFrameQueueItemFinishedEvent", 1)
    )
    assert [frame.frame_index for frame in driven.traces._frame_render_traces] == [1, 2, 3, 4, 5, 6]


# save shorter than the device stage; several times it, and fewer saves at once than slots; more
# than all the slots together can keep up with
SAVE_REGIMES = {
    "saves keep up on one thread": (0.04, 0.01),
    "saves overlap": (0.02, 0.06),
    "every save slot taken": (0.01, 0.01 * SAVE_FRAMES * 3),
}
save_regimes = pytest.mark.parametrize("device_seconds,save_seconds", SAVE_REGIMES.values(), ids=SAVE_REGIMES.keys())


@both_backends
@save_regimes
def test_never_more_than_two_frames_on_the_device_and_save_frames_saving(
    tmp_path, make_backend, device_seconds, save_seconds
):
    """Two issued and uncollected where the backend parts issue from collect, one in its
    device stage where it cannot (a save stage alone does not make a backend issue ahead);
    up to `SAVE_FRAMES` frames saving either way, and a second one only where a frame's pixels
    arrive while a save is under way; `DEVICE_FRAMES + SAVE_FRAMES` in hand at most."""
    backend = make_backend(tmp_path, device_seconds=device_seconds, save_seconds=save_seconds)
    frames = 2 * SAVE_FRAMES + 4
    driven = render_all(backend, frames)
    limits = {"device": 2 if make_backend is IssueAheadBackend else 1, "save": SAVE_FRAMES}
    open_stages = {"device": 0, "save": 0}
    for what, _frame, _at in sorted(backend.log, key=lambda entry: entry[2]):
        stage, _, edge = what.partition("_")
        if stage in open_stages and edge == "start":
            open_stages[stage] += 1
            assert open_stages[stage] <= limits[stage], f"{open_stages[stage]} frames in their {stage} stage"
        elif stage in open_stages and edge == "end":
            open_stages[stage] -= 1
    assert backend.on_device() == limits["device"]
    most = most_rendering(driven)
    assert most <= limits["device"] + SAVE_FRAMES
    waited = driven.counter("worker_loop_seconds_total", state="save_wait")
    beside_save = driven.counter("worker_frames_saved_beside_save_total")
    save_threads = {thread.name for thread in driven.queue._saver._threads}
    assert backend.save_threads == save_threads <= {f"frame-save_{n}" for n in range(SAVE_FRAMES)}
    assert [event.frame_index for event in driven.sender.finished()] == list(range(1, frames + 1))
    if save_seconds < device_seconds:
        # the parent's loop: one frame saving, on the one thread that was ever started
        assert backend.most_at_once("save") == 1 and beside_save == 0
        assert save_threads == {"frame-save_0"}
        assert most == limits["device"] + 1
        assert waited < 0.05
    elif save_seconds < SAVE_FRAMES * device_seconds:
        # a save lasts three device stages: about three saving at once, a slot always free (five, so that
        # a loaded machine may keep the loop from its turn for 0.1 s)
        assert 2 <= backend.most_at_once("save") < SAVE_FRAMES
        assert 2 <= len(save_threads) < SAVE_FRAMES
        assert limits["device"] + 1 < most < limits["device"] + SAVE_FRAMES
        assert beside_save >= frames - 3  # every frame but the first and the last ones
        assert waited < 0.05  # save_wait only once all slots are busy, and they never are
    else:
        # the saves, all slots at once, are slower than the device: the pipeline is full
        assert backend.most_at_once("save") == SAVE_FRAMES == len(save_threads)
        assert most == limits["device"] + SAVE_FRAMES
        assert beside_save == frames - 1
        # the second SAVE_FRAMES frames wait for the first ones' saves to end, less the time it
        # took to start those one device stage apart
        assert waited > 0.5 * (save_seconds - (SAVE_FRAMES + 2) * device_seconds)
        # and no frame waited before every slot was taken: the first SAVE_FRAMES saves began as
        # their pixels arrived
        device_end, save_start = backend.times("device_end"), backend.times("save_start")
        assert all(save_start[frame] - device_end[frame] < 0.25 * save_seconds for frame in range(1, SAVE_FRAMES + 1))
        assert save_start[SAVE_FRAMES + 1] - device_end[SAVE_FRAMES + 1] > 0.25 * save_seconds


@both_backends
def test_with_nothing_queued_the_save_starts_at_once_and_nothing_is_counted(tmp_path, make_backend):
    backend = make_backend(tmp_path)
    job = make_job("one-at-a-time", 4)

    async def body(driven: Driven) -> None:
        for frame in range(1, 5):  # the next frame arrives on the finished event, as naive-fine sends it
            driven.queue.queue_frame(job, frame)
            await until(lambda: len(driven.sender.finished()) == frame)

    driven = drive(backend, body)
    device_end, save_start = backend.times("device_end"), backend.times("save_start")
    assert all(save_start[frame] - device_end[frame] < 0.25 for frame in range(1, 5))
    assert backend.on_device() == 1
    assert driven.counter("worker_frames_saved_beside_render_total") == 0
    assert driven.counter("worker_frames_issued_ahead_total") == 0  # 0 of frames sent one by one
    assert driven.counter("worker_loop_seconds_total", state="save_wait") == 0


@both_backends
def test_a_frame_that_arrives_during_a_save_renders_beside_it(tmp_path, make_backend):
    backend = make_backend(tmp_path, device_seconds=0.01, save_seconds=0.25)
    job = make_job("late-arrival", 2)

    async def body(driven: Driven) -> None:
        driven.queue.queue_frame(job, 1)
        await until(lambda: 1 in backend.times("save_start"))
        driven.queue.queue_frame(job, 2)
        await until(lambda: len(driven.sender.finished()) == 2)

    driven = drive(backend, body)
    assert backend.times("device_start")[2] < backend.times("save_end")[1]
    assert driven.counter("worker_frames_saved_beside_render_total") == 1
    assert driven.counter("worker_frames_issued_ahead_total") == 0  # frame 1 had been collected
    assert [event.frame_index for event in driven.sender.finished()] == [1, 2]


def test_a_frame_that_arrives_during_a_wait_is_issued_before_that_wait_returns(tmp_path):
    backend = IssueAheadBackend(tmp_path, device_seconds=0.3)
    job = make_job("arrives-mid-wait", 2)

    async def body(driven: Driven) -> None:
        driven.queue.queue_frame(job, 1)
        await until(lambda: 1 in backend.times("collect_start"))
        driven.queue.queue_frame(job, 2)  # the collect thread is blocked; the issue thread is not
        await until(lambda: 2 in backend.times("dispatched"))
        assert 1 not in backend.times("device_end")
        await until(lambda: len(driven.sender.finished()) == 2)

    driven = drive(backend, body, directory=tmp_path)
    assert backend.times("dispatched")[2] < backend.times("device_end")[1]
    # the fake device ran the two back to back: the second was in its queue when the first ended
    assert backend.times("device_end")[2] - backend.times("device_end")[1] < 0.3 + 0.25
    assert driven.counter("worker_frames_issued_ahead_total") == 1
    assert [event.frame_index for event in driven.sender.finished()] == [1, 2]
    assert all(there for _, message, there in driven.sender.sent
               if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent))


@both_backends
def test_a_failing_save_errors_that_frame_alone(tmp_path, make_backend):
    backend = make_backend(tmp_path, fail_saves=frozenset({2}))
    driven = render_all(backend, 4, directory=tmp_path)
    finished = driven.sender.finished()
    assert [event.frame_index for event in finished] == [1, 2, 3, 4]
    assert [event.result for event in finished] == ["ok", "errored", "ok", "ok"]
    assert "disk full" in finished[1].error_reason
    assert sorted(path.name for path in tmp_path.iterdir()) == ["1.bin", "3.bin", "4.bin"]
    assert driven.counter("worker_frames_errored_total") == 1
    assert driven.counter("worker_frames_rendered_total") == 3
    job_name = "two-stage"
    assert driven.queue.unqueue_frame(job_name, 2) == pm.FRAME_QUEUE_REMOVE_RESULT_ERRORED  # not in the finished index
    assert driven.queue.unqueue_frame(job_name, 3) == pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_FINISHED
    # the frame in its device stage while frame 2's save failed went on undisturbed
    assert backend.times("device_start")[3] < backend.times("save_end")[2] < backend.times("device_end")[3]


@pytest.mark.parametrize(
    "make_backend,fails",
    [(TwoStageBackend, "fail_devices"), (IssueAheadBackend, "fail_devices"), (IssueAheadBackend, "fail_collects")],
    ids=["device stage", "issue", "collect"],
)
def test_a_failing_device_stage_errors_that_frame_alone_and_in_order(tmp_path, make_backend, fails):
    backend = make_backend(tmp_path, **{fails: frozenset({2})})
    driven = render_all(backend, 4, directory=tmp_path)
    finished = driven.sender.finished()
    # in order: the error leaves after frame 1's file is in place and its event sent
    assert [event.frame_index for event in finished] == [1, 2, 3, 4]
    assert [event.result for event in finished] == ["ok", "errored", "ok", "ok"]
    assert "frame 2 failed" in finished[1].error_reason
    assert sorted(path.name for path in tmp_path.iterdir()) == ["1.bin", "3.bin", "4.bin"]
    assert all(there for _, message, there in driven.sender.sent
               if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent) and message.result == "ok")
    assert driven.counter("worker_frames_errored_total") == 1
    # the frames behind it were issued and collected all the same
    assert {3, 4} <= set(backend.times("device_end"))


@both_backends
def test_a_later_frames_save_that_ends_first_is_reported_second_and_its_file_is_whole_before_its_event(
    tmp_path, make_backend
):
    """Files may appear out of frame order; finished events do not leave out of it."""
    backend = make_backend(tmp_path, device_seconds=0.01, save_seconds=0.02, slow_saves={1: 0.5, 4: 0.3})
    driven = render_all(backend, 6, directory=tmp_path)
    save_end = backend.times("save_end")
    sent_at = {
        message.frame_index: (at, there) for at, message, there in driven.sender.sent
        if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent)
    }
    # frames 2 and 3 were whole on disk long before frame 1, and frames 5 and 6 before frame 4 ...
    assert max(save_end[2], save_end[3]) < save_end[1] - 0.2
    assert max(save_end[5], save_end[6]) < save_end[4] - 0.1
    # ... and each waited its turn: every event after the event of the frame before, and after its own file
    assert [event.frame_index for event in driven.sender.finished()] == [1, 2, 3, 4, 5, 6]
    assert all(event.result == pm.FRAME_QUEUE_ITEM_FINISHED_OK for event in driven.sender.finished())
    for frame in range(1, 7):
        at, there = sent_at[frame]
        assert there and at >= save_end[frame]
        assert frame == 1 or at >= sent_at[frame - 1][0]
    assert sent_at[2][0] >= save_end[1] and sent_at[5][0] >= save_end[4]
    assert [frame.frame_index for frame in driven.traces._frame_render_traces] == [1, 2, 3, 4, 5, 6]
    assert driven.counter("worker_frames_saved_beside_save_total") == 5
    # a frame that waited its turn with its file in place was not held: a slot was free when its pixels came
    assert driven.metrics.snapshot()["worker_frame_held_seconds"]["series"][""]["sum"] == 0.0


@pytest.mark.parametrize(
    "make_backend,fails",
    [(TwoStageBackend, "fail_saves"), (IssueAheadBackend, "fail_saves"), (TwoStageBackend, "fail_devices"),
     (IssueAheadBackend, "fail_devices"), (IssueAheadBackend, "fail_collects")],
    ids=["save, one on the device", "save, two on the device", "device stage", "issue", "collect"],
)
def test_a_failing_stage_errors_that_frame_alone_and_in_order_with_other_frames_saving(tmp_path, make_backend, fails):
    """Saves of fifteen device stages: when frame 4 fails, in whichever stage, the three frames
    ahead of it are saving and its error waits its turn behind their finished events; the frames
    behind it are issued, saved and reported as if nothing had happened."""
    backend = make_backend(tmp_path, device_seconds=0.01, save_seconds=0.15, **{fails: frozenset({4})})
    driven = render_all(backend, 9, directory=tmp_path)
    finished = driven.sender.finished()
    assert [event.frame_index for event in finished] == list(range(1, 10))
    assert [event.result for event in finished] == ["ok"] * 3 + ["errored"] + ["ok"] * 5
    assert ("disk full" if fails == "fail_saves" else "frame 4 failed") in finished[3].error_reason
    assert sorted(path.name for path in tmp_path.iterdir()) == [f"{frame}.bin" for frame in (1, 2, 3, 5, 6, 7, 8, 9)]
    assert all(there for _, message, there in driven.sender.sent
               if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent) and message.result == "ok")
    assert driven.counter("worker_frames_errored_total") == 1
    assert driven.counter("worker_frames_rendered_total") == 8
    assert backend.most_at_once("save") >= 3
    # the failure came while other frames were saving (the ones behind it, where it is the save that
    # fails at its end; the ones ahead of it otherwise), and left after the events of the frames ahead
    if fails == "fail_saves":
        assert backend.times("save_start")[6] < backend.times("save_end")[4] < backend.times("save_end")[5]
    else:
        assert backend.times("device_start")[4] < backend.times("save_end")[1]
    errored_at = next(at for at, message, _ in driven.sender.sent
                      if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent) and message.frame_index == 4)
    assert errored_at >= backend.times("save_end")[3]
    assert driven.queue.unqueue_frame("two-stage", 4) == pm.FRAME_QUEUE_REMOVE_RESULT_ERRORED  # not in the finished index
    assert driven.queue.unqueue_frame("two-stage", 5) == pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_FINISHED


def in_hand(tmp_path: Path, make_backend, then, saving: int = 1) -> tuple[TwoStageBackend, Driven, int]:
    """Queue two frames more than the loop can hold and call
    `then(driven, backend, job, held)` at a moment when the first `saving`
    frames are saving and the frames behind them are in their device stage
    (one, or two where the backend issues ahead: `held` frames in hand),
    none of them done."""
    if saving == 1:
        backend = make_backend(tmp_path, device_seconds=0.3, save_seconds=0.25)
    else:
        backend = make_backend(tmp_path, device_seconds=0.03, save_seconds=1.5, slow_saves={1: 1.8})
    held = saving + (2 if make_backend is IssueAheadBackend else 1)
    job = make_job("in-hand", held + 2)

    async def body(driven: Driven) -> None:
        for frame in range(1, held + 3):
            driven.queue.queue_frame(job, frame)
        await until(lambda: saving in backend.times("save_start") and held in backend.times("dispatched"))
        assert not backend.times("save_end") and saving + 1 not in backend.times("save_start")
        await then(driven, backend, job, held)

    return backend, drive(backend, body, directory=tmp_path), held


# one frame saving (the parent's loop), and every save slot taken with the oldest save the
# last to end
frames_saving = pytest.mark.parametrize("saving", [1, SAVE_FRAMES], ids=["one saving", "every slot saving"])


@both_backends
@frames_saving
def test_unqueue_answers_already_rendering_in_either_stage(tmp_path, make_backend, saving):
    answers = {}

    async def then(driven, backend, job, held):
        for frame in range(1, held + 2):
            answers[frame] = driven.queue.unqueue_frame(job.job_name, frame)
        await until(lambda: len(driven.sender.finished()) == held + 1)

    _backend, driven, held = in_hand(tmp_path, make_backend, then, saving)
    # saving, or on the device (the frame running there and the one issued behind it)
    assert [answers[frame] for frame in range(1, held + 1)] == [pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_RENDERING] * held
    assert answers[held + 1] == pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED
    assert [event.frame_index for event in driven.sender.finished()] == [*range(1, held + 1), held + 2]


@both_backends
@frames_saving
def test_drain_waits_for_every_frame_in_hand_and_hands_back_the_rest(tmp_path, make_backend, saving):
    returned = []

    async def then(driven, backend, job, held):
        returned.extend(await driven.queue.drain())
        # every frame in hand is finished, files in place, events sent, when drain returns
        assert set(backend.times("save_end")) == set(range(1, held + 1))
        assert [event.frame_index for event in driven.sender.finished()] == list(range(1, held + 1))
        with pytest.raises(RuntimeError):
            driven.queue.queue_frame(job, 9)

    backend, driven, held = in_hand(tmp_path, make_backend, then, saving)
    assert [(name, unit.frame_index) for name, unit in returned] == [("in-hand", held + 1), ("in-hand", held + 2)]
    assert set(backend.times("device_start")) == set(range(1, held + 1))  # nothing started after the drain began
    assert all(there for _, message, there in driven.sender.sent
               if isinstance(message, pm.WorkerFrameQueueItemFinishedEvent))


@both_backends
@frames_saving
def test_reset_session_fences_a_saving_frame_as_it_fences_a_rendering_one(tmp_path, make_backend, saving):
    async def then(driven, backend, job, held):
        assert driven.queue.reset_session() == 2  # the last two were queued, not started
        await until(lambda: len(driven.sender.finished()) == held)
        # every finished event went out (the new master refuses them by their epoch) ...
        assert [event.frame_index for event in driven.sender.finished()] == list(range(1, held + 1))
        # ... and none of the frames entered the new session's finished index
        for frame in range(1, held + 1):
            assert driven.queue.unqueue_frame(job.job_name, frame) == pm.FRAME_QUEUE_REMOVE_RESULT_ERRORED
        # a frame of the new session is indexed as ever
        driven.queue.queue_frame(job, held + 1)
        await until(lambda: len(driven.sender.finished()) == held + 1)
        assert driven.queue.unqueue_frame(job.job_name, held + 1) == pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_FINISHED

    in_hand(tmp_path, make_backend, then, saving)


@both_backends
def test_joining_mid_pipeline_leaves_no_thread_blocked(tmp_path, make_backend):
    backend = make_backend(tmp_path, device_seconds=0.05, save_seconds=0.05)
    job = make_job("cut-short", 6)

    async def body(driven: Driven) -> None:
        for frame in range(1, 7):
            driven.queue.queue_frame(job, frame)
        # one frame saving (or about to) and the device stage full behind it
        await until(lambda: 1 in backend.times("device_end") and 3 in backend.times("device_start"))

    before = {thread for thread in threading.enumerate()}
    drive(backend, body)
    deadline = time.perf_counter() + 5.0
    while time.perf_counter() < deadline:
        # the save thread, and the issue and collect threads where the backend has the pair
        left = [t for t in threading.enumerate() if t not in before and t.name.startswith("frame-")]
        if not left:
            break
        time.sleep(0.01)
    assert not left
    # what was under way ended on its own; nothing was begun after the join
    assert len(backend.times("device_start")) <= 5


@both_backends
def test_joining_with_several_frames_saving_and_gated_leaves_no_thread_blocked_and_no_unit_lost(tmp_path, make_backend):
    """A slow dispatch keeps the saves handed over behind it gated (two at once where the backend
    issues ahead): the loop is cut with saves under way, saves that have not begun and a dispatch
    in the issue thread's hands."""
    backend = make_backend(tmp_path, dispatch_seconds=0.15, device_seconds=0.01, save_seconds=0.4)
    job = make_job("cut-short-saving", 12)
    left = {}

    async def body(driven: Driven) -> None:
        for frame in range(1, 13):
            driven.queue.queue_frame(job, frame)

        def several_saving_and_one_gated() -> bool:
            saving = driven.queue._saving
            return (
                sum(not s.future.done() and s.gate.is_set() for s in saving) >= 2
                and any(not s.gate.is_set() for s in saving)
            )

        await until(several_saving_and_one_gated)
        left["frames"] = list(driven.queue._frames)
        left["gated"] = [s.frame.frame_index for s in driven.queue._saving if not s.gate.is_set()]

    before = set(threading.enumerate())
    driven = drive(backend, body, directory=tmp_path)
    deadline = time.perf_counter() + 10.0
    while [t for t in threading.enumerate() if t not in before and t.name.startswith("frame-")]:
        assert time.perf_counter() < deadline, "a stage's thread is still blocked"
        time.sleep(0.01)
    assert left["gated"]
    # every gate was opened: a gated save ran to its file, or was never begun (cancelled with the executor)
    finished = [event.frame_index for event in driven.sender.finished()]
    assert finished == list(range(1, len(finished) + 1))
    in_hand_at_the_cut = [f.frame_index for f in left["frames"]]
    assert finished + in_hand_at_the_cut == list(range(1, 13))  # finished, or still the queue's: none gone
    assert all(f.state in (FrameState.RENDERING, FrameState.QUEUED) for f in left["frames"])
    assert not [path for path in tmp_path.iterdir() if path.suffix != ".bin"]


def test_a_backend_with_no_save_stage_goes_through_the_same_loop_one_frame_at_a_time():
    backend = MockBackend(load_seconds=0.001, render_seconds=0.01, save_seconds=0.004)
    driven = render_all(backend, 4)
    assert backend.rendered_frames == [1, 2, 3, 4]
    frames = [trace.details for trace in driven.traces._frame_render_traces]
    assert all(later.started_process_at >= earlier.exited_process_at
               for earlier, later in zip(frames, frames[1:]))
    assert driven.counter("worker_frames_saved_beside_render_total") == 0
    assert driven.counter("worker_frames_issued_ahead_total") == 0
    assert driven.counter("worker_loop_seconds_total", state="save_wait") == 0
    # every event of frame i before any event of frame i+1, as it always was
    order = [message.frame_index for _, message, _ in driven.sender.sent]
    assert order == [1, 1, 2, 2, 3, 3, 4, 4]
    assert not [t for t in threading.enumerate() if t.name.startswith(("frame-issue", "frame-collect"))]


def test_the_series_are_exposed_at_zero_from_the_workers_start():
    async def body(driven: Driven) -> None:
        await asyncio.sleep(0)

    driven = drive(MockBackend(), body)
    snapshot = driven.metrics.snapshot()
    assert snapshot["worker_frames_saved_beside_render_total"]["series"]
    assert snapshot["worker_frames_issued_ahead_total"]["series"]
    states = {key.removeprefix("state=") for key in snapshot["worker_loop_seconds_total"]["series"]}
    assert states == set(LOOP_STATES) == {"no_work", "render_call", "report", "save_wait"}
    text = render_prometheus(snapshot)
    assert "worker_frames_saved_beside_render_total 0" in text
    assert "worker_frames_issued_ahead_total 0" in text
    assert 'worker_loop_seconds_total{state="save_wait"} 0' in text


def test_the_saved_beside_save_series_is_exposed_at_zero_and_no_save_thread_is_started_before_a_save():
    async def body(driven: Driven) -> None:
        await asyncio.sleep(0)
        assert not driven.queue._saver._threads  # none until a save is handed over

    driven = drive(MockBackend(), body)
    snapshot = driven.metrics.snapshot()
    assert snapshot["worker_frames_saved_beside_save_total"]["series"] == {"": 0.0}
    assert "worker_frames_saved_beside_save_total 0" in render_prometheus(snapshot)
    # a backend with no save stage of its own never starts one either, and counts nothing
    driven = render_all(MockBackend(load_seconds=0.001, render_seconds=0.005, save_seconds=0.001), 3)
    assert not driven.queue._saver._threads
    assert driven.counter("worker_frames_saved_beside_save_total") == 0
    assert SAVE_FRAMES == 8 and driven.queue._saver._max_workers == SAVE_FRAMES


@both_backends
@save_regimes
def test_the_four_loop_states_add_up_to_the_loops_wall_time_under_overlap(
    tmp_path, make_backend, device_seconds, save_seconds
):
    backend = make_backend(tmp_path, device_seconds=device_seconds, save_seconds=save_seconds)
    frames = 2 * SAVE_FRAMES + 4
    job = make_job("four-states", frames)

    async def body(driven: Driven) -> None:
        while driven.queue._loop_state != "no_work":
            await asyncio.sleep(0.001)
        await asyncio.sleep(0.15)  # nothing queued yet: the loop starves, all of this sleep long
        for frame in range(1, frames + 1):
            driven.queue.queue_frame(job, frame)
        await until(lambda: len(driven.sender.finished()) == frames)

    driven = drive(backend, body)
    by_state = {state: driven.counter("worker_loop_seconds_total", state=state) for state in LOOP_STATES}
    # the partition, against the loop's own clock (tests/test_steps.py has why)
    assert sum(by_state.values()) == pytest.approx(driven.wall, abs=1e-6)
    # each state from the side its sleeps guarantee
    assert by_state["no_work"] >= 0.15
    if save_seconds > SAVE_FRAMES * device_seconds:
        # twenty saves of 0.24 s, eight at a time, behind device stages of 0.01 s: the pipeline is full
        # from the eighth hand-over until the first save ends (and again for the next eight)
        assert by_state["save_wait"] > 0.5 * (save_seconds - (SAVE_FRAMES + 2) * device_seconds)
    else:
        assert by_state["save_wait"] < 0.05  # a slot is always free
    assert by_state["render_call"] > 0.5 * save_seconds  # the last save, at least, with nothing else to start
    assert by_state["report"] > 0
    assert by_state["no_work"] + by_state["save_wait"] + by_state["render_call"] <= driven.wall


@both_backends
@pytest.mark.parametrize("save_seconds,frames", [(0.008, 6), (0.12, 14)], ids=["one save slot", "several save slots"])
def test_the_overlapped_timeline_passes_the_trace_validator(tmp_path, make_backend, save_seconds, frames):
    backend = make_backend(tmp_path / "frames", device_seconds=0.02, save_seconds=save_seconds)
    driven = render_all(backend, frames)
    path = driven.tracer.export(tmp_path / "worker-test_trace-events.json")
    assert validate_trace_file(path) == []
    events = [e for e in driven.tracer.events() if e.get("cat") == "worker"]
    tracks = {
        m["args"]["name"]: m["tid"] for m in driven.tracer.metadata_events() if m["name"] == "thread_name"
    }
    write_tracks = {e["tid"] for e in events if e["name"] == "write"}
    step_events = [e for e in driven.tracer.events() if e.get("cat") == "worker.step" and e["name"] in ("encode", "file_write")]
    # the write phase and the save steps go by save slot, the lowest free one: the slots' tracks in use are the
    # first ones, and on no slot's track do two writes overlap
    slots = {tracks[name]: slot for slot, (name, _steps) in enumerate(SAVE_TRACKS) if name in tracks}
    assert write_tracks == set(slots) and sorted(slots.values()) == list(range(len(slots)))
    # (the fake stages time no steps; a backend that does has them on the slot's second track)
    assert {e["tid"] for e in step_events} == {tracks[steps] for name, steps in SAVE_TRACKS if name in tracks and steps in tracks}
    for tid in write_tracks:
        on_track = sorted((e["ts"], e["ts"] + e["dur"]) for e in events if e["name"] == "write" and e["tid"] == tid)
        assert all(later[0] >= earlier[1] for earlier, later in zip(on_track, on_track[1:]))
    if save_seconds < 0.02:
        # saves shorter than device stages: the first slot's track (a second one only where a loaded machine
        # kept the loop from its turn for a device stage), and nearly every write on it
        on_first = [e for e in events if e["name"] == "write" and e["tid"] == tracks["saves"]]
        assert len(on_first) >= frames - 2
    else:
        # saves of six device stages: several slots, and most writes overlap in time
        assert 3 <= len(slots) <= SAVE_FRAMES
        in_time = sorted((e["ts"], e["ts"] + e["dur"]) for e in events if e["name"] == "write")
        assert sum(later[0] < earlier[1] for earlier, later in zip(in_time, in_time[1:])) >= frames - 3
    device_tracks = {e["tid"] for e in events if e["name"] in ("read", "render")}
    if make_backend is TwoStageBackend:
        assert device_tracks == {tracks["frames"]}
    else:
        # two frames on the device: a frame issued behind an uncollected one is drawn on the second track
        assert device_tracks == {tracks["frames"], tracks["frames, second on device"]}
    # a frame's write lies under the next frame's render: that is why it has a track of its own
    writes = {e["args"]["frame"]: e for e in events if e["name"] == "write"}
    renders = {e["args"]["frame"]: e for e in events if e["name"] == "render"}
    assert any(
        renders[frame + 1]["ts"] < writes[frame]["ts"] + writes[frame]["dur"] for frame in range(1, frames)
    )
    # and on no track do two render spans overlap, though consecutive frames' do in time
    for tid in device_tracks:
        on_track = sorted((e["ts"], e["ts"] + e["dur"]) for e in renders.values() if e["tid"] == tid)
        assert all(later[0] >= earlier[1] - 1.0 for earlier, later in zip(on_track, on_track[1:]))
    if make_backend is IssueAheadBackend:
        in_time = sorted((e["ts"], e["ts"] + e["dur"]) for e in renders.values())
        assert any(later[0] < earlier[1] for earlier, later in zip(in_time, in_time[1:]))


# -- the tpu-raytrace backend through the pipeline ---------------------------------------


@pytest.fixture(scope="module")
def raytraced(tmp_path_factory):
    """Three whole frames (JPEG) and the four tiles of a fourth (PNG) of the
    sphere scene at 32x32, through the queue; the pixels each save stage was
    handed are kept beside."""
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    base = tmp_path_factory.mktemp("pipeline")

    class KeepsPixels(TpuRaytraceBackend):
        def __init__(self, **kwargs) -> None:
            super().__init__(**kwargs)
            self.pixels: dict[tuple[int, int | None], np.ndarray] = {}

        def _save_stage(self, job, frame_index, tile, pixels, **rendered):
            self.pixels[(frame_index, tile)] = np.array(pixels)
            return super()._save_stage(job, frame_index, tile, pixels, **rendered)

    backend = KeepsPixels(base_directory=base, width=32, height=32, samples=1, max_bounces=2)
    whole = make_job("04_very-simple_pipeline", 4)
    tiled = dataclasses.replace(whole, tile_grid=(2, 2))
    backend._render_sync(whole, 1)  # both programs built before the timed frames
    backend._render_sync(tiled, 4, 0)
    backend.pixels.clear()

    async def body(driven: Driven) -> None:
        for frame in (1, 2, 3):
            driven.queue.queue_frame(whole, frame)
        for tile in range(4):
            driven.queue.queue_frame(tiled, 4, tile=tile)
        await until(lambda: len(driven.sender.finished()) == 7, 120.0)

    driven = drive(backend, body)
    return base, backend, driven


def test_a_frame_and_a_tile_through_the_pipeline_are_write_images_bytes(raytraced, tmp_path):
    from tpu_render_cluster.render.image_io import write_image

    base, backend, driven = raytraced
    assert all(event.result == "ok" for event in driven.sender.finished())
    written = sorted((base / "out").iterdir())
    assert [path.name for path in written] == [
        "rendered-00001.jpg", "rendered-00002.jpg", "rendered-00003.jpg",
        "rendered-00004.tile_r0c0.png", "rendered-00004.tile_r0c1.png",
        "rendered-00004.tile_r1c0.png", "rendered-00004.tile_r1c1.png",
    ]
    units = [(1, None), (2, None), (3, None), (4, 0), (4, 1), (4, 2), (4, 3)]
    for path, unit in zip(written, units):
        again = tmp_path / path.name
        write_image(again, backend.pixels[unit], "PNG" if unit[1] is not None else "JPEG")
        assert path.read_bytes() == again.read_bytes()
    assert backend.pixels[(1, None)].shape == (32, 32, 3) and backend.pixels[(4, 0)].shape == (16, 16, 3)
    assert not [path for path in (base / "out").iterdir() if path.name.endswith(".tmp")]


def test_a_pipelined_frames_timing_holds_all_six_steps_and_feeds_the_series(raytraced):
    _base, _backend, driven = raytraced
    traces = driven.traces._frame_render_traces
    assert len(traces) == 7
    for trace in traces:
        timing = trace.details
        names = [name for name, _, _, _ in timing.steps]
        assert names == list(FRAME_STEPS[:4]) + ["file_write", "encode", "file_write"]
        points = [
            timing.started_process_at, timing.finished_loading_at, timing.started_rendering_at,
            timing.finished_rendering_at, timing.file_saving_started_at,
            timing.file_saving_finished_at, timing.exited_process_at,
        ]
        assert points == sorted(points)
        # the save stage's steps lie inside the write phase, the others before it
        for name, start, seconds, _cpu in timing.steps:
            if name in ("encode", "file_write"):
                assert timing.file_saving_started_at <= start
                assert start + seconds <= timing.file_saving_finished_at + 1e-3
            else:
                assert start + seconds <= timing.finished_rendering_at + 1e-3
    snapshot = driven.metrics.snapshot()
    by_step = {
        key.removeprefix("step="): entry
        for key, entry in snapshot["worker_frame_step_seconds"]["series"].items()
    }
    assert set(by_step) == set(FRAME_STEPS)
    assert by_step["device_wait"]["count"] == 7 and by_step["encode"]["count"] == 7
    assert by_step["file_write"]["count"] == 14
    phases = snapshot["worker_frame_phase_seconds"]["series"]
    assert phases["phase=render"]["count"] == 7 and phases["phase=write"]["count"] == 7
    # the save steps lie inside the write phase (what a loaded machine puts between two
    # steps of a 32x32 frame, a thread waiting for the GIL, is in the phase and in no step)
    write = sum(t.details.file_saving_finished_at - t.details.file_saving_started_at for t in traces)
    assert 0 < by_step["encode"]["sum"] + by_step["file_write"]["sum"] <= write
    assert phases["phase=write"]["sum"] == pytest.approx(write)
    by_state = {state: driven.counter("worker_loop_seconds_total", state=state) for state in LOOP_STATES}
    assert sum(by_state.values()) == pytest.approx(driven.wall, abs=0.1)
    assert driven.counter("worker_frames_saved_beside_render_total") == 6  # all but the last
    assert driven.counter("worker_frames_rendered_total") == 7


def test_four_frames_issued_back_to_back_are_the_files_of_four_frames_rendered_one_by_one(raytraced, tmp_path):
    """Nothing is donated and no frame's buffers are another's: two frames' work in the device's
    queue at once leaves each file what `_render_sync` (issue, collect and save back to back, one
    frame in the process at a time) writes for that frame, byte for byte."""
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    _base, _backend, _driven = raytraced  # the programs are built
    job = make_job("04_very-simple_back-to-back", 4)
    one_by_one = TpuRaytraceBackend(base_directory=tmp_path / "sync", width=32, height=32, samples=1, max_bounces=2)
    for frame in range(1, 5):
        one_by_one._render_sync(job, frame)
    queued = TpuRaytraceBackend(base_directory=tmp_path / "queued", width=32, height=32, samples=1, max_bounces=2)
    driven = render_all(queued, 4, job=job)
    assert [event.result for event in driven.sender.finished()] == ["ok"] * 4
    # at most: on a loaded machine a 32x32 frame may be collected before the next is issued (ROADMAP D20 ii)
    assert driven.counter("worker_frames_issued_ahead_total") <= 3
    names = [f"rendered-{frame:05d}.jpg" for frame in range(1, 5)]
    assert sorted(path.name for path in (tmp_path / "queued" / "out").iterdir()) == names
    for name in names:
        assert (tmp_path / "queued" / "out" / name).read_bytes() == (tmp_path / "sync" / "out" / name).read_bytes()
    # the frames differ from each other (the frame number seeds the streams), so equal bytes are each frame's own
    assert len({(tmp_path / "sync" / "out" / name).read_bytes() for name in names}) == 4
    # a frame's resolve and dispatch were timed where it was issued, its wait and copy where it was collected
    for trace in driven.traces._frame_render_traces:
        assert [name for name, _, _, _ in trace.details.steps][:4] == list(FRAME_STEPS[:4])
    frames = [trace.details for trace in driven.traces._frame_render_traces]
    assert any(later.started_rendering_at < earlier.finished_rendering_at for earlier, later in zip(frames, frames[1:]))


def test_eight_frames_saved_at_once_are_the_files_of_eight_frames_saved_one_by_one(raytraced, tmp_path):
    """A file written beside seven others is byte for byte the file written alone: every save
    waits at a barrier for `SAVE_FRAMES` saves to have begun, so all of them encode, write and
    rename at once, each on a save thread of its own, and feed the backend's series from there."""
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    _base, _backend, _driven = raytraced  # the programs are built
    job = make_job("04_very-simple_saved-at-once", SAVE_FRAMES, file_format="PNG")
    shape = dict(width=32, height=32, samples=1, max_bounces=2)
    one_by_one = TpuRaytraceBackend(base_directory=tmp_path / "sync", **shape)
    for frame in range(1, SAVE_FRAMES + 1):
        one_by_one._render_sync(job, frame)

    all_begun = threading.Barrier(SAVE_FRAMES, timeout=120.0)
    save_threads = set()

    class SavesTogether(TpuRaytraceBackend):
        def _save_stage(self, *unit, **rendered):
            save_threads.add(threading.current_thread().name)
            all_begun.wait()
            return super()._save_stage(*unit, **rendered)

    queued = SavesTogether(base_directory=tmp_path / "queued", **shape)
    tier_frames = queued._tier_frames.value(tier="masked")
    family_frames = queued._family_frames.value(family="04_very-simple")
    switch_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # the threads change hands within a statement's reach of each other
    try:
        driven = render_all(queued, SAVE_FRAMES, job=job)
    finally:
        sys.setswitchinterval(switch_interval)
    assert [event.result for event in driven.sender.finished()] == ["ok"] * SAVE_FRAMES
    assert [event.frame_index for event in driven.sender.finished()] == list(range(1, SAVE_FRAMES + 1))
    assert save_threads == {f"frame-save_{n}" for n in range(SAVE_FRAMES)}
    assert driven.counter("worker_frames_saved_beside_save_total") == SAVE_FRAMES - 1
    names = [f"rendered-{frame:05d}.png" for frame in range(1, SAVE_FRAMES + 1)]
    assert sorted(path.name for path in (tmp_path / "queued" / "out").iterdir()) == names  # and no temporary file
    for name in names:
        assert (tmp_path / "queued" / "out" / name).read_bytes() == (tmp_path / "sync" / "out" / name).read_bytes()
    assert len({(tmp_path / "sync" / "out" / name).read_bytes() for name in names}) == SAVE_FRAMES
    # what the saves fed from their eight threads lost no update
    assert queued._tier_frames.value(tier="masked") == tier_frames + SAVE_FRAMES
    assert queued._family_frames.value(family="04_very-simple") == family_frames + SAVE_FRAMES
    assert driven.counter("worker_frame_pixel_bytes_total") == SAVE_FRAMES * 32 * 32 * 3
    # eight saves at once took the eight slots: each frame's write and save steps on its slot's pair of tracks
    assert validate_trace_file(driven.tracer.export(tmp_path / "worker-test_trace-events.json")) == []
    tracks = {m["args"]["name"]: m["tid"] for m in driven.tracer.metadata_events() if m["name"] == "thread_name"}
    spans = driven.tracer.events()
    assert {e["tid"] for e in spans if e["name"] == "write"} == {tracks[saves] for saves, _ in SAVE_TRACKS}
    assert {e["tid"] for e in spans if e["name"] == "encode"} == {tracks[steps] for _, steps in SAVE_TRACKS}
    # each frame's own steps, in its own order, though eight threads took steps at once
    for trace in driven.traces._frame_render_traces:
        assert [name for name, _, _, _ in trace.details.steps] == list(FRAME_STEPS[:4]) + ["file_write", "encode", "file_write"]


# -- the trace of record under overlap ---------------------------------------------------


def frame_at(start: float, *, read=1.0, render=2.0, gap=0.0, save=0.5, after=0.5) -> FrameRenderTime:
    rendered = start + read + render
    return FrameRenderTime(
        started_process_at=start,
        finished_loading_at=start + read,
        started_rendering_at=start + read,
        finished_rendering_at=rendered,
        file_saving_started_at=rendered + gap,
        file_saving_finished_at=rendered + gap + save,
        exited_process_at=rendered + gap + save + after,
    )


def trace_of(frames: list[FrameRenderTime], start: float, finish: float) -> WorkerTrace:
    return WorkerTrace(
        total_queued_frames=len(frames),
        total_queued_frames_removed_from_queue=0,
        job_start_time=start,
        job_finish_time=finish,
        frame_render_traces=[WorkerFrameTrace(index + 1, frame) for index, frame in enumerate(frames)],
        ping_traces=[],
        reconnection_traces=[],
    )


def test_the_reducer_counts_idle_as_the_time_no_frame_covers():
    # Four frames of 4 s each (3 s to the pixels, 1 s of saving), each begun
    # when the one before has its pixels: starts 105, 108, 111; then a gap
    # of 2 s that no frame covers, a frame at 117, and a tail.
    frames = [frame_at(105.0), frame_at(108.0), frame_at(111.0), frame_at(117.0)]
    trace = trace_of(frames, 100.0, 124.0)
    performance = WorkerPerformance.from_worker_trace(trace)  # raised "Idle time between frames is negative"
    assert performance.total_frames_rendered == 4
    assert performance.total_blend_file_reading_time == pytest.approx(4.0)
    assert performance.total_rendering_time == pytest.approx(8.0)
    assert performance.total_image_saving_time == pytest.approx(2.0)
    # lead-in 5, nothing between the overlapped three, tail 124 - 121 = 3; the
    # last frame's gap to its predecessor is not counted, as in the reference
    assert performance.total_idle_time == pytest.approx(8.0)
    # with one more frame behind, that gap of 2 s (115 -> 117) is a middle one and counts
    longer = trace_of(frames + [frame_at(121.0)], 100.0, 128.0)
    assert WorkerPerformance.from_worker_trace(longer).total_idle_time == pytest.approx(5.0 + 2.0 + 3.0)


def test_the_reducer_reads_a_serial_trace_as_it_always_did():
    frames = [frame_at(105.0), frame_at(111.0), frame_at(118.0)]
    performance = WorkerPerformance.from_worker_trace(trace_of(frames, 100.0, 126.0))
    # lead-in 5 + gap (111 - 109) 2 + tail (126 - 122) 4; tests/test_traces.py has the same numbers
    assert performance.total_idle_time == pytest.approx(11.0)
    assert performance.total_time == 26.0
    assert performance.total_rendering_time == pytest.approx(6.0)


def job_trace_of(worker: WorkerTrace):
    from tpu_render_cluster.analysis.models import JobTrace

    return JobTrace(
        job=make_job("utilization", 4), job_started_at=worker.job_start_time,
        job_finished_at=worker.job_finish_time, worker_traces={"worker-a": worker},
    )


def test_utilization_is_the_union_of_the_frames_and_at_most_one():
    from tpu_render_cluster.analysis.metrics import worker_utilizations
    from tpu_render_cluster.analysis.models import mean_frame_time, worker_active_time

    # ten frames of 4 s, a new one every 3 s: the sum of their durations is 40 s of a 31 s window
    frames = [frame_at(100.0 + 3.0 * index) for index in range(10)]
    overlapped = trace_of(frames, 100.0, 131.0)
    assert sum(frame.total_execution_time() for frame in frames) == pytest.approx(40.0)
    assert worker_active_time(overlapped) == pytest.approx(31.0)
    assert mean_frame_time(overlapped) == pytest.approx(4.0)
    serial = trace_of([frame_at(105.0), frame_at(111.0), frame_at(118.0)], 100.0, 126.0)
    assert worker_active_time(serial) == pytest.approx(12.0)  # the plain sum, as before
    assert mean_frame_time(serial) == pytest.approx(4.0)
    (utilization,) = worker_utilizations(job_trace_of(overlapped))
    assert utilization.utilization == pytest.approx(1.0) and utilization.utilization <= 1.0
    assert utilization.utilization_without_tail <= 1.0
    (serial_utilization,) = worker_utilizations(job_trace_of(serial))
    assert serial_utilization.utilization == pytest.approx(12.0 / 26.0)


# -- a whole job through `master run-job` ------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_a_job_run_to_its_end_writes_its_processed_results_from_an_overlapped_trace(tmp_path):
    frames_dir = tmp_path / "frames"
    job_path = tmp_path / "job.toml"
    job_path.write_text(f'''
job_name = "04_very-simple_overlap"
job_description = "save beside render, through run-job"
project_file_path = "%BASE%/p.blend"
render_script_path = "%BASE%/s.py"
frame_range_from = 1
frame_range_to = 8
wait_for_number_of_workers = 1
output_directory_path = "{frames_dir}"
output_file_name_format = "rendered-####"
output_file_format = "JPEG"

[frame_distribution_strategy]
strategy_type = "eager-naive-coarse"
target_queue_size = 8
''')
    port = free_port()
    results = tmp_path / "results"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    master = subprocess.Popen(
        [sys.executable, "-m", "tpu_render_cluster.master.main", "--host", "127.0.0.1",
         "--port", str(port), "run-job", str(job_path), "--resultsDirectory", str(results)],
        env=env,
    )
    worker = subprocess.Popen(
        [sys.executable, "-m", "tpu_render_cluster.worker.main", "--masterServerHost", "127.0.0.1",
         "--masterServerPort", str(port), "--baseDirectory", str(tmp_path), "--backend", "tpu-raytrace",
         "--renderSize", "32x32", "--renderSamples", "2", "--warmScene", "04_very-simple"],
        env=env,
    )
    try:
        assert master.wait(timeout=300) == 0
        worker.wait(timeout=60)
    finally:
        for process in (worker, master):
            if process.poll() is None:
                process.kill()
    assert len(list(frames_dir.glob("rendered-*.jpg"))) == 8
    raw = json.loads(next(results.glob("*_raw-trace.json")).read_text())
    (worker_trace,) = raw["worker_traces"].values()
    frames = [entry["details"] for entry in worker_trace["frame_render_traces"]]
    assert [entry["frame_index"] for entry in worker_trace["frame_render_traces"]] == list(range(1, 9))
    # the trace of record IS overlapped: a frame began before the one before it had left
    assert any(later["started_process_at"] < earlier["exited_process_at"]
               for earlier, later in zip(frames, frames[1:]))
    processed = json.loads(next(results.glob("*_processed-results.json")).read_text())
    (performance,) = processed["worker_performance"].values()
    assert performance["total_frames_rendered"] == 8
    assert 0.0 <= performance["total_idle_time"] <= performance["total_time"]
    assert validate_trace_file(next(results.glob("*_cluster_trace-events.json"))) == []
