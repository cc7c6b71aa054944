"""Worker-side automatic render queue.

Reference: ``WorkerAutomaticQueue`` (worker/src/rendering/queue.rs:16-230) —
a 100 ms poll loop takes the first Queued frame, marks it Rendering, renders
it, then emits the finished event and pops it.

Three deliberate deviations:
- the ``event_frame-queue_item-started-rendering`` event IS emitted (the
  reference defines and handles it but never sends it, SURVEY.md §3.3);
- a render failure emits ``event_frame-queue_item-finished`` with
  ``errored`` instead of silently dropping the frame (which would hang the
  reference master forever — worker/src/rendering/queue.rs:169-174);
- **a frame is two stages, and up to ``DEVICE_FRAMES + SAVE_FRAMES``
  frames are in hand at a time**: the device stage (to the pixels on the
  host) and the save stage (encode, write, rename;
  ``worker/backends/base.py``). A frame's save stage runs on a save
  thread beside the device stages of the frames behind it, and beside the
  saves of the frames ahead of it where those have not ended: up to
  ``SAVE_FRAMES`` frames are saving at once, each on a thread that is
  started only when a save is handed over and no save thread is free (a
  worker whose saves are shorter than its device stages has one). Where
  the backend parts the device stage into issue and collect, up to
  ``DEVICE_FRAMES`` frames are in it at once: a queued frame's device
  work is issued (on the issue thread) as soon as fewer than two frames
  are issued and not yet handed to their save, whether or not a wait for
  an earlier frame is under way (on the collect thread), so the device
  finds frame *i+1* in its queue the moment frame *i* ends. Frames are
  collected, handed to their save and reported in the order they were
  issued, so up to ``DEVICE_FRAMES + SAVE_FRAMES`` units are
  ``RENDERING``. A finished event still leaves only after its frame's
  file has been renamed into place, and finished events leave in the
  order the frames were rendered: only the oldest saving frame is ever
  taken in, and a save that ends before an earlier frame's waits its
  turn (so FILES may appear on disk out of frame order, each whole:
  ``write_image`` renames). The ``rendering`` events of the frames
  behind frame *i* may precede its finished event. A backend that cannot
  part issue from collect has one frame in its device stage at a time,
  and one with no separable save stage goes through the same loop one
  whole frame at a time.
"""

from __future__ import annotations

import asyncio
import enum
import logging
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.obs import CPU_TIMED_STEPS, FILE_WRITE_OPS, MetricsRegistry, Tracer
from tpu_render_cluster.obs.startup import get_startup
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.transport.actors import SenderHandle
from tpu_render_cluster.traces.worker_trace import WorkerTraceBuilder
from tpu_render_cluster.utils.cancellation import CancellationToken
from tpu_render_cluster.traces.worker_trace import FrameRenderTime
from tpu_render_cluster.worker.backends.base import RenderBackend, RenderedFrame

logger = logging.getLogger(__name__)

QUEUE_POLL_SECONDS = 0.1  # reference: worker/src/rendering/queue.rs:74-96

# The per-frame phase breakdown the paper's analysis is built around
# (reading/rendering/writing), plus the queue-wait the paper only derives
# post-hoc from trace gaps — here measured directly.
FRAME_PHASES = ("queue_wait", "read", "render", "write")

# Frames a backend that parts issue from collect may have in their device
# stage at once: one running on the device and one in its queue behind
# it. A constant of the loop, as SAVE_FRAMES is.
DEVICE_FRAMES = 2

# Frames that may be in their save stage at once, each on a save thread of
# its own: a save is handed over as soon as fewer than this many frames
# are saving (a save that has ended and waits behind an earlier frame's
# to be taken in still counts). Eight: a lossless 512x512 frame takes one
# encoder 86 ms and the device 13 ms (ledger PR 52, `04vs-1w-png`), so
# the device's pace needs six or seven encoders busy at once. Pixels in
# hand are then at most DEVICE_FRAMES + SAVE_FRAMES frames'.
SAVE_FRAMES = 8

# The render loop's wall time, partitioned (worker_loop_seconds_total). The
# loop is one coroutine with up to DEVICE_FRAMES + SAVE_FRAMES frames in
# hand, and what it is charged is what IT waits for or does, not what a
# frame costs:
#   no_work      nothing queued and no stage in hand; waiting for the
#                master (draining excluded)
#   render_call  waiting for a backend stage with nothing else to start:
#                a frame's device stage (thread hop included), or, with
#                nothing queued, the save stages of the last frames. The
#                frames' steps (obs.FRAME_STEPS) lie in it; the save of
#                frame i mostly under the device stages of the frames
#                behind it
#   report       the loop's own work: the rendering/finished events, trace
#                bookkeeping, feeding the phase and step series
#   save_wait    the pipeline is full: a frame's device stage has returned
#                and SAVE_FRAMES frames are in their save stage, so that
#                frame's save cannot start, nor a queued frame take its
#                place in the device stage (the saves, all of them at
#                once, slower than render)
LOOP_STATES = ("no_work", "render_call", "report", "save_wait")

# The steps of the save stage (of obs.FRAME_STEPS): they and the ``write``
# phase go on timeline tracks of their own, because frame i's lie under
# frame i+1's device steps and a track's spans must not overlap. For the
# same reason the device stage's phases and steps have two tracks each:
# a frame issued while the frame before it was uncollected takes the
# track that frame does not lie on. And the save stage's have a pair of
# tracks a save slot: a frame keeps the lowest slot no saving frame has
# from the hand-over to the moment it is taken in, and its ``write`` lies
# between the two.
SAVE_STEPS = ("encode", "file_write")
DEVICE_TRACKS = (("frames", "steps"), ("frames, second on device", "steps, second on device"))
SAVE_TRACKS = (("saves", "save steps"),) + tuple(
    (f"saves, slot {slot}", f"save steps, slot {slot}") for slot in range(2, SAVE_FRAMES + 1)
)

# ``held``: the one stretch of a frame's life that is no step and no phase.
# From the end of its ``readback`` (``finished_rendering_at``: its pixels
# are on the host) to the start of its save stage
# (``file_saving_started_at``), WHERE EVERY SAVE SLOT WAS TAKEN when the
# pixels arrived; 0 where a slot was free by then (the hand-over to a save
# thread and the wait behind the next frame's dispatch are the loop's
# ``report`` and no hold). One observation a frame
# (worker_frame_held_seconds) and, where it is not 0, one span. A frame is
# held under the ``write`` of the frames ahead of it, and where two frames
# are on the device the frame behind it is held at the same time, so the
# spans have two tracks of their own, taken in turn.
HELD_TRACKS = ("held", "held, second frame")

# The label values of worker_frame_file_bytes_total{format}, each at 0 from
# the worker's start: what render/image_io.py::written_format can answer
# (written out here because importing the render package imports JAX, and a
# worker with another backend never does; a test holds the two equal).
FILE_FORMATS = ("BMP", "JPEG", "PNG", "TIFF")

# The label values of worker_process_cpu_seconds_total{mode}: the two
# fields of ``os.times()`` that are this process's own (its children's are
# left out), all of its threads together.
PROCESS_CPU_MODES = ("user", "system")


class FrameState(enum.Enum):
    QUEUED = "queued"
    RENDERING = "rendering"
    FINISHED = "finished"


@dataclass
class QueuedFrame:
    job: BlenderJob
    frame_index: int
    state: FrameState = FrameState.QUEUED
    queued_at: float = field(default_factory=time.time)
    # Trace context from the master's queue-add request (None from a
    # reference-shaped master); echoed on rendering/finished events and
    # routed through the phase spans as a Perfetto flow.
    trace: pm.TraceContext | None = None
    # Scheduler job id from the queue-add request (None from single-job
    # masters); echoed on rendering/finished events.
    job_id: str | None = None
    # Sub-frame tile index from the queue-add request (None = whole
    # frame); echoed on rendering/finished events.
    tile: int | None = None
    # Master epoch from the queue-add request (None from epoch-less
    # masters); echoed on rendering/finished events so a successor master
    # can fence out a predecessor's assignments after a failover.
    epoch: int | None = None
    # Worker-local session generation at queue time (see reset_session).
    session: int = 0
    # What the loop saw of the frame's way through the stages: its device
    # work was issued while an earlier frame's had not been collected;
    # which of DEVICE_TRACKS its device stage is drawn on; a later frame's
    # device stage was open while its save ran; an earlier frame's save
    # had not ended when its own was handed over, and which of SAVE_TRACKS
    # (its save slot) its save stage is drawn on.
    issued_ahead: bool = False
    device_track: int = 0
    saved_beside_render: bool = False
    saved_beside_save: bool = False
    save_slot: int = 0
    # Wall time since which a save slot was free for this frame (a frame
    # was taken in while all of them were taken, or they never all were),
    # and which of HELD_TRACKS its hold is drawn on.
    save_free_at: float = 0.0
    held_track: int = 0

    @property
    def unit(self) -> WorkUnit:
        return WorkUnit(self.frame_index, self.tile)


@dataclass
class _DeviceFrame:
    """A frame in its device stage: issued, or being issued, and not yet
    handed to its save."""

    frame: QueuedFrame
    # the stage's RenderedFrame (or the whole frame's FrameRenderTime from
    # a backend that cannot part the two), or what the stage raised
    future: asyncio.Future


@dataclass
class _SavingFrame:
    """A frame in its save stage, or past it and behind an earlier frame
    that is not: it keeps its save slot until it is taken in."""

    frame: QueuedFrame
    # the frame's FrameRenderTime, or what the save raised (or, already
    # there, what came of the device stage of a frame that has no save)
    future: asyncio.Future
    # what the save waits behind: the next frame's dispatch, or nothing
    gate: threading.Event = field(default_factory=threading.Event)


def _outcome(future: asyncio.Future) -> object:
    """What a stage came to: its result, or the exception it raised."""
    try:
        return future.result()
    except Exception as e:  # noqa: BLE001 - reported as the frame's error
        return e


class WorkerAutomaticQueue:
    """Two-stage render queue: up to two frames in their device stage (one
    where the backend cannot issue ahead), up to ``SAVE_FRAMES`` frames
    before them in their save stage; woken by events, polled every 100 ms."""

    def __init__(
        self,
        backend: RenderBackend,
        sender: SenderHandle,
        tracer: WorkerTraceBuilder,
        cancellation: CancellationToken,
        *,
        metrics: MetricsRegistry | None = None,
        span_tracer: Tracer | None = None,
    ) -> None:
        self._backend = backend
        self._sender = sender
        self._tracer = tracer
        self._cancellation = cancellation
        self._metrics = metrics
        self._span_tracer = span_tracer
        self._phase_histogram = (
            metrics.histogram(
                "worker_frame_phase_seconds",
                "Per-frame phase durations (queue_wait/read/render/write)",
                labels=("phase",),
            )
            if metrics is not None
            else None
        )
        self._step_histogram = (
            metrics.histogram(
                "worker_frame_step_seconds",
                "Exclusive steps of a frame on the render thread "
                "(resolve/dispatch/device_wait/readback/encode/file_write), "
                "one observation per uninterrupted stretch",
                labels=("step",),
            )
            if metrics is not None
            else None
        )
        self._step_cpu_seconds = (
            metrics.counter(
                "worker_frame_step_cpu_seconds_total",
                "CPU seconds of the step's own thread (time.thread_time) "
                "inside the steps of worker_frame_step_seconds that have a "
                "CPU clock (obs.CPU_TIMED_STEPS: device_wait/encode/"
                "file_write): a step's wall seconds less these are time "
                "its thread did not run",
                labels=("step",),
            )
            if metrics is not None
            else None
        )
        self._file_write_op_seconds = (
            metrics.counter(
                "worker_file_write_op_seconds_total",
                "Wall seconds of the file_write step by file system "
                "operation (obs.FILE_WRITE_OPS: mkdir/create/write/close/rename)",
                labels=("op",),
            )
            if metrics is not None
            else None
        )
        self._process_cpu = (
            metrics.counter(
                "worker_process_cpu_seconds_total",
                "CPU seconds of the worker's process, all its threads and "
                "none of its children, by mode (user/system; os.times), "
                "brought up to date as each frame is reported",
                labels=("mode",),
            )
            if metrics is not None
            else None
        )
        self._loop_seconds = (
            metrics.counter(
                "worker_loop_seconds_total",
                "Wall time of the render loop by state "
                "(no_work/render_call/report/save_wait)",
                labels=("state",),
            )
            if metrics is not None
            else None
        )
        self._saved_beside_render = (
            metrics.counter(
                "worker_frames_saved_beside_render_total",
                "Frames whose save stage (encode, write, rename) ran while "
                "a later frame's device stage was open",
            )
            if metrics is not None
            else None
        )
        self._issued_ahead = (
            metrics.counter(
                "worker_frames_issued_ahead_total",
                "Frames whose device work was issued while an earlier "
                "frame's had not been collected",
            )
            if metrics is not None
            else None
        )
        self._saved_beside_save = (
            metrics.counter(
                "worker_frames_saved_beside_save_total",
                "Frames whose save stage began while an earlier frame's "
                "save had not ended",
            )
            if metrics is not None
            else None
        )
        self._held_histogram = (
            metrics.histogram(
                "worker_frame_held_seconds",
                "Per frame, from its pixels on the host (end of readback) "
                "to the start of its save stage where every save slot was "
                "taken; 0 where a save slot was free",
            )
            if metrics is not None
            else None
        )
        self._pixel_bytes = (
            metrics.counter(
                "worker_frame_pixel_bytes_total",
                "Raw u8 pixel bytes handed to the encode step",
            )
            if metrics is not None
            else None
        )
        self._file_bytes = (
            metrics.counter(
                "worker_frame_file_bytes_total",
                "Encoded bytes renamed into place, by the format written",
                labels=("format",),
            )
            if metrics is not None
            else None
        )
        if metrics is not None:
            # Exposed at 0 from the start: a scrape that finds no series
            # could not tell "never happened" from "not counted".
            self._saved_beside_render.inc(0.0)
            self._issued_ahead.inc(0.0)
            self._saved_beside_save.inc(0.0)
            for state in LOOP_STATES:
                self._loop_seconds.inc(0.0, state=state)
            self._held_histogram.expose()
            self._pixel_bytes.inc(0.0)
            for image_format in FILE_FORMATS:
                self._file_bytes.inc(0.0, format=image_format)
            for name in CPU_TIMED_STEPS:
                self._step_cpu_seconds.inc(0.0, step=name)
            for op in FILE_WRITE_OPS:
                self._file_write_op_seconds.inc(0.0, op=op)
            self._note_process_cpu()
            # What worker_process_cpu_seconds_total can rise by a second:
            # the CPUs this process may run on.
            metrics.gauge(
                "worker_host_cpu_units",
                "CPUs the worker's process may run on (sched_getaffinity)",
            ).set(len(os.sched_getaffinity(0)))
        # The save stage's threads: a thread is started only when a save
        # is handed over and none of them is free, so there is one where
        # saves are shorter than device stages, and none until the backend
        # hands back a RenderedFrame.
        self._saver = ThreadPoolExecutor(max_workers=SAVE_FRAMES, thread_name_prefix="frame-save")
        # One thread that issues and one that collects, for a backend that
        # parts the two: each takes its frames in the queue's order, and a
        # frame is issued while the wait for the one before it blocks the
        # other thread (the GIL released). Never started for a backend
        # that cannot.
        self._issuer = ThreadPoolExecutor(max_workers=1, thread_name_prefix="frame-issue")
        self._collector = ThreadPoolExecutor(max_workers=1, thread_name_prefix="frame-collect")
        # (getattr: one that is no RenderBackend cannot part the two either)
        self._issue = getattr(backend, "issue_device_stage", None)
        self._device_frames = 1 if self._issue is None else DEVICE_FRAMES
        # The frames in their device stage and the frames in their save
        # stage, each in the order they were issued.
        self._on_device: deque[_DeviceFrame] = deque()
        self._saving: deque[_SavingFrame] = deque()
        # Wall time since which a save slot is free (``held`` above), and
        # how many saves have begun (the holds' tracks, in turn).
        self._save_free_at = 0.0
        self._saves_begun = 0
        self._loop_state: str | None = None
        self._loop_state_since = time.perf_counter()
        self._startup = get_startup()
        self._frames: list[QueuedFrame] = []
        self._finished_indices: set[tuple[str, int, int | None]] = set()
        # Bumped by reset_session(): a frame queued under a previous
        # master session that only finishes rendering AFTER the reset
        # must not re-enter the finished index (the new master may
        # legitimately re-assign that unit).
        self._session_generation = 0
        self._task: asyncio.Task | None = None
        self._draining = False
        # Wakes the render loop as soon as work arrives; the 100 ms sleep
        # remains only as a fallback poll (the reference burns up to a full
        # poll interval of idle time per queue refill — queue.rs:74-96).
        self._work_available = asyncio.Event()

    # -- queue interface (called from the message manager) -------------------

    def queue_frame(
        self,
        job: BlenderJob,
        frame_index: int,
        *,
        trace: pm.TraceContext | None = None,
        job_id: str | None = None,
        tile: int | None = None,
        epoch: int | None = None,
    ) -> None:
        if self._draining:
            # Refuse, don't silently park: the add RPC answers errored and
            # the master returns the frame to the pending pool — a frame
            # accepted here after drain() collected the queue would be lost.
            raise RuntimeError("Worker is draining; not accepting new frames.")
        if not self._startup.finished:
            self._startup.enter("first_frame")
        self._frames.append(
            QueuedFrame(
                job, frame_index, trace=trace, job_id=job_id, tile=tile,
                epoch=epoch, session=self._session_generation,
            )
        )
        self._work_available.set()

    def unqueue_frame(
        self, job_name: str, frame_index: int, tile: int | None = None
    ) -> str:
        """Returns the frame-queue-remove result enum wire value.

        Reference: worker/src/rendering/queue.rs:192-229. ``tile`` rides
        the same optional piggyback as queue-add: a tiled steal removes
        exactly one tile, and whole-frame requests (tile None) only ever
        match whole-frame entries.
        """
        if (job_name, frame_index, tile) in self._finished_indices:
            return pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_FINISHED
        for i, frame in enumerate(self._frames):
            if (
                frame.job.job_name == job_name
                and frame.frame_index == frame_index
                and frame.tile == tile
            ):
                if frame.state is FrameState.RENDERING:
                    return pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_RENDERING
                if frame.state is FrameState.FINISHED:
                    return pm.FRAME_QUEUE_REMOVE_RESULT_ALREADY_FINISHED
                del self._frames[i]
                return pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED
        return pm.FRAME_QUEUE_REMOVE_RESULT_ERRORED

    def queue_size(self) -> int:
        return len(self._frames)

    async def drain(self) -> list[tuple[str, int]]:
        """Graceful drain: finish the frames in hand, hand back the rest.

        Stops the loop from starting new frames, waits for the ones in their
        device stage and the ones in their save stage to complete (their
        finished events go out normally), and returns the ``(job_name, frame_index)`` pairs that
        never started — the payload of the goodbye message the runtime
        sends so the master can requeue them without waiting for a
        heartbeat-timeout eviction.
        """
        self._draining = True
        self._work_available.set()  # wake the loop so it parks promptly
        while any(f.state is FrameState.RENDERING for f in self._frames):
            await asyncio.sleep(0.01)
        returned = [
            (f.job.job_name, f.unit)
            for f in self._frames
            if f.state is FrameState.QUEUED
        ]
        self._frames = [f for f in self._frames if f.state is not FrameState.QUEUED]
        return returned

    def reset_session(self) -> int:
        """Drop the previous master session's queue state (failover).

        Called when the worker re-announces itself to a NEW master
        incarnation (epoch change / refused reconnect): the queued-but-
        not-started frames belong to assignments the new master does not
        know about, so replaying them would render work nobody tracks.
        A frame currently RENDERING (in its device stage or in its save
        stage: there may be ``DEVICE_FRAMES`` and ``SAVE_FRAMES``) is left
        to finish — its finished event carries the OLD epoch and the new
        master refuses it as stale, which is the fence working as
        designed. The already-finished index is cleared too: the new
        master may legitimately
        re-assign a unit this worker rendered for the predecessor, and an
        ``already-finished`` answer to a later remove RPC would lie about
        the NEW assignment. Returns how many queued frames were dropped.
        """
        dropped = [f for f in self._frames if f.state is FrameState.QUEUED]
        self._frames = [
            f for f in self._frames if f.state is not FrameState.QUEUED
        ]
        self._finished_indices.clear()
        # A frame left mid-RENDER or mid-save belongs to the OLD session:
        # when it finishes, it must not re-enter the just-cleared finished index
        # (the generation check at insert time fences it out).
        self._session_generation += 1
        return len(dropped)

    # -- render loop ---------------------------------------------------------

    def start(self) -> None:
        self._task = asyncio.create_task(self._run(), name="render-queue")

    async def join(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        # a stage under way ends on its own, as a render thread's always
        # did; one that has not begun never does
        for threads in (self._issuer, self._collector, self._saver):
            threads.shutdown(wait=False, cancel_futures=True)

    def _next_queued(self) -> QueuedFrame | None:
        for frame in self._frames:
            if frame.state is FrameState.QUEUED:
                return frame
        return None

    def _enter_loop_state(self, state: str | None) -> None:
        """Charge the time since the last call to the state the loop was
        in, then move to ``state`` (None: time nobody is charged for)."""
        now = time.perf_counter()
        if self._loop_seconds is not None and self._loop_state is not None:
            self._loop_seconds.inc(
                now - self._loop_state_since, state=self._loop_state
            )
        self._loop_state = state
        self._loop_state_since = now

    def _note_process_cpu(self) -> None:
        """Bring the process's CPU counter up to ``os.times()``: the counter
        is its own memory, so a second queue on the same registry (a worker
        that moved to another master) goes on where the first one stopped."""
        if self._process_cpu is None:
            return
        times = os.times()
        for mode in PROCESS_CPU_MODES:
            counted = self._process_cpu.value(mode=mode)
            self._process_cpu.inc(max(0.0, getattr(times, mode) - counted), mode=mode)

    async def _run(self) -> None:
        try:
            await self._run_loop()
        finally:
            self._enter_loop_state(None)
            self._note_process_cpu()
            for in_stage in self._on_device:
                in_stage.future.cancel()
            for saving in self._saving:
                saving.gate.set()  # no thread is left blocked behind it

    async def _run_loop(self) -> None:
        while not self._cancellation.is_cancelled():
            # Cleared before anything is looked at: whatever ends or
            # arrives from here on wakes the wait at the bottom.
            self._work_available.clear()
            # What has ended is taken in first, the save before the device
            # stage, and of the saving frames the oldest alone: finished
            # events leave in the order of the frames, and a save that
            # ended before an earlier frame's waits its turn.
            if self._saving and self._saving[0].future.done():
                if len(self._saving) == SAVE_FRAMES:
                    self._save_free_at = time.time()
                saved = self._saving.popleft()
                await self._report(saved.frame, _outcome(saved.future))
                continue
            # The oldest frame on the device is the only one looked at:
            # whatever came of the ones behind it waits its turn.
            rendered = bool(self._on_device) and self._on_device[0].future.done()
            if rendered and len(self._saving) < SAVE_FRAMES:
                head = self._on_device.popleft()
                outcome = _outcome(head.future)
                if not isinstance(outcome, RenderedFrame):
                    # No save to begin (the device stage raised, or gave
                    # the whole frame): it is reported when its turn comes
                    # behind the saving frames, and nothing is issued
                    # ahead of that.
                    ended = asyncio.get_running_loop().create_future()
                    ended.set_result(outcome)
                    self._enter_save_stage(_SavingFrame(head.frame, ended))
                    continue
                # The hand-over, in this order: the next frame's device
                # work is issued FIRST and this frame's save begins behind
                # it (encoding holds the GIL the dispatch needs). With
                # nothing queued there is nothing to wait behind.
                saving = self._begin_save(head.frame, outcome)
                next_frame = self._next_to_issue()
                if next_frame is None:
                    saving.gate.set()
                else:
                    await self._begin_device_stage(next_frame, saving.gate.set)
                continue
            next_frame = self._next_to_issue()
            if next_frame is not None:
                await self._begin_device_stage(next_frame, lambda: None)
                continue
            if rendered:
                self._enter_loop_state("save_wait")
            elif self._on_device or self._saving:
                self._enter_loop_state("render_call")
            else:
                # Fed at every poll, so a scrape is never more than one
                # poll interval behind on a starved worker.
                self._enter_loop_state(None if self._draining else "no_work")
            try:
                await asyncio.wait_for(
                    self._work_available.wait(), QUEUE_POLL_SECONDS
                )
            except asyncio.TimeoutError:
                pass

    def _next_to_issue(self) -> QueuedFrame | None:
        """The queued frame whose device stage may begin now, if any."""
        if self._draining or len(self._on_device) >= self._device_frames:
            return None
        return self._next_queued()

    def _wake(self, _ended: asyncio.Future) -> None:
        self._work_available.set()

    async def _begin_device_stage(self, frame: QueuedFrame, dispatched) -> None:
        self._enter_loop_state("report")
        frame.state = FrameState.RENDERING
        for saving in self._saving:
            if not saving.future.done():
                saving.frame.saved_beside_render = True
        if any(not earlier.future.done() for earlier in self._on_device):
            frame.issued_ahead = True
            frame.device_track = 1 - self._on_device[-1].frame.device_track
        await self._sender.send_message(
            pm.WorkerFrameQueueItemRenderingEvent(
                frame.job.job_name, frame.frame_index, trace=frame.trace,
                job_id=frame.job_id, tile=frame.tile, epoch=frame.epoch,
            )
        )
        if self._issue is None:
            stage = self._backend.render_device_stage(
                frame.job, frame.frame_index, tile=frame.tile, dispatched=dispatched
            )
        else:
            stage = self._issue_and_collect(frame, dispatched)
        future = asyncio.ensure_future(stage)
        # a stage that ends, however it ends, lets the save behind it go
        future.add_done_callback(lambda _ended: dispatched())
        future.add_done_callback(self._wake)
        self._on_device.append(_DeviceFrame(frame, future))

    async def _issue_and_collect(self, frame: QueuedFrame, dispatched) -> RenderedFrame:
        """A frame's device stage in its two parts, each on its thread."""

        def issue():
            try:
                return self._issue(frame.job, frame.frame_index, frame.tile)
            finally:
                dispatched()  # from the issue thread: no turn of the loop between

        loop = asyncio.get_running_loop()
        issued = await loop.run_in_executor(self._issuer, issue)
        return await loop.run_in_executor(self._collector, issued.collect)

    def _begin_save(self, frame: QueuedFrame, rendered: RenderedFrame) -> _SavingFrame:
        gate = threading.Event()
        # a frame issued behind this one is in its device stage already
        frame.saved_beside_render = bool(self._on_device)
        frame.saved_beside_save = any(not ahead.future.done() for ahead in self._saving)
        frame.save_free_at = self._save_free_at
        frame.held_track = self._saves_begun % len(HELD_TRACKS)
        self._saves_begun += 1

        def save() -> FrameRenderTime:
            gate.wait()
            return rendered.save()

        future = asyncio.get_running_loop().run_in_executor(self._saver, save)
        future.add_done_callback(self._wake)
        return self._enter_save_stage(_SavingFrame(frame, future, gate))

    def _enter_save_stage(self, saving: _SavingFrame) -> _SavingFrame:
        """Behind the saving frames, on the lowest save slot none of them
        has (there is one: fewer than ``SAVE_FRAMES`` are saving)."""
        taken = {ahead.frame.save_slot for ahead in self._saving}
        saving.frame.save_slot = min(set(range(SAVE_FRAMES)) - taken)
        self._saving.append(saving)
        return saving

    async def _report(self, frame: QueuedFrame, outcome: object) -> None:
        """A frame's end: its file is in place (``outcome`` is its seven
        points) or one of its stages raised (``outcome`` is the error)."""
        self._enter_loop_state("report")
        job_name = frame.job.job_name
        if not isinstance(outcome, FrameRenderTime):
            logger.error("Unit %s render failed: %s", frame.unit.label, outcome)
            if self._metrics is not None:
                self._metrics.counter(
                    "worker_frames_errored_total", "Frames that failed to render"
                ).inc()
            # NOT added to _finished_indices: the master returns errored
            # frames to the pending pool and may re-queue them here; a later
            # remove request must not answer "already-finished".
            self._remove(frame)
            await self._sender.send_message(
                pm.WorkerFrameQueueItemFinishedEvent.new_errored(
                    job_name, frame.frame_index, str(outcome), trace=frame.trace,
                    job_id=frame.job_id, tile=frame.tile, epoch=frame.epoch,
                )
            )
            return
        timing = outcome
        if not self._startup.finished:
            self._startup.finish()  # the first frame's file is in place
        self._tracer.trace_new_rendered_frame(frame.frame_index, timing)
        self._observe_frame_phases(frame, timing)
        if self._metrics is not None:
            if frame.saved_beside_render:
                self._saved_beside_render.inc()
            if frame.issued_ahead:
                self._issued_ahead.inc()
            if frame.saved_beside_save:
                self._saved_beside_save.inc()
        self._remove(frame)
        if frame.session == self._session_generation:
            # A frame queued under a PREVIOUS master session (failover hit
            # while it rendered) stays out of the index: the new master
            # may re-assign this unit, and an "already-finished" answer to
            # a later remove RPC would lie about the NEW assignment.
            self._finished_indices.add(
                (job_name, frame.frame_index, frame.tile)
            )
        await self._sender.send_message(
            pm.WorkerFrameQueueItemFinishedEvent.new_ok(
                job_name, frame.frame_index, trace=frame.trace,
                job_id=frame.job_id, tile=frame.tile, epoch=frame.epoch,
            )
        )

    def _observe_frame_phases(self, frame: QueuedFrame, timing) -> None:
        """Feed the live per-phase histograms + emit retroactive spans.

        The spans reuse the 7-point wall-clock timestamps the backend
        already measured (the trace of record), so the Perfetto view and
        the legacy ``FrameRenderTime`` analysis agree exactly. A frame's
        ``write`` lies under the next frame's ``read`` and ``render`` and
        beside the ``write`` of other frames, so it has a track of its
        save slot's (``SAVE_TRACKS``), and so have the save stage's
        steps; a frame issued behind an
        uncollected one has its device stage on the second of
        ``DEVICE_TRACKS``; its hold (``HELD_TRACKS``) lies between its
        ``render`` and its ``write``.
        """
        if self._metrics is None and self._span_tracer is None:
            return
        frames_track, steps_track = DEVICE_TRACKS[frame.device_track]
        saves_track, save_steps_track = SAVE_TRACKS[frame.save_slot]
        bounds = {
            "queue_wait": (frame.queued_at, timing.started_process_at),
            "read": (timing.started_process_at, timing.finished_loading_at),
            "render": (timing.started_rendering_at, timing.finished_rendering_at),
            "write": (timing.file_saving_started_at, timing.file_saving_finished_at),
        }
        for phase in FRAME_PHASES:
            start, end = bounds[phase]
            duration = max(0.0, end - start)
            if self._phase_histogram is not None:
                self._phase_histogram.observe(duration, phase=phase)
            if self._span_tracer is not None:
                # the job by name: a frame number alone does not say whose
                # frame this worker rendered once several jobs share it
                args = {"frame": frame.frame_index, "job": frame.job.job_name}
                if frame.tile is not None:
                    args["tile"] = frame.tile
                if frame.trace is not None:
                    args["flow"] = frame.trace.flow_id
                track = saves_track if phase == "write" else frames_track
                self._span_tracer.complete(
                    phase,
                    cat="worker",
                    start_wall=start,
                    duration=duration,
                    track=track,
                    args=args,
                )
                if frame.trace is not None:
                    # Route the assignment's flow through each phase span
                    # (mid-span so it binds even to zero-length phases):
                    # the master's assign span started it; its
                    # result-received span will terminate it.
                    flow_args = {"frame": frame.frame_index, "phase": phase}
                    if frame.tile is not None:
                        flow_args["tile"] = frame.tile
                    self._span_tracer.flow_step(
                        "frame",
                        id=frame.trace.flow_id,
                        ts=start + duration / 2.0,
                        cat="frame",
                        track=track,
                        args=flow_args,
                    )
        # The frame's steps, its hold and its bytes enter the registry and
        # the timeline together with its phases, so a scrape never sees
        # half a frame. A category and tracks of their own: readers of the
        # phase spans (cat "worker") see the spans they always saw.
        held = 0.0
        if frame.save_free_at > timing.finished_rendering_at:
            held = max(0.0, timing.file_saving_started_at - timing.finished_rendering_at)
        if self._held_histogram is not None:
            self._held_histogram.observe(held)
        if held > 0.0 and self._span_tracer is not None:
            self._span_tracer.complete(
                "held",
                cat="worker.step",
                start_wall=timing.finished_rendering_at,
                duration=held,
                track=HELD_TRACKS[frame.held_track],
                args={"frame": frame.frame_index},
            )
        step_bytes = {}
        write_ops_ms = {}
        if timing.saved is not None:
            image_format, pixel_bytes, file_bytes, write_op_seconds = timing.saved
            if self._metrics is not None:
                self._pixel_bytes.inc(pixel_bytes)
                self._file_bytes.inc(file_bytes, format=image_format)
                for op, seconds in zip(FILE_WRITE_OPS, write_op_seconds):
                    self._file_write_op_seconds.inc(seconds, op=op)
            # what each save step took in and gave out: the encoder's
            # bytes are all written, and all renamed into place
            step_bytes = {
                "encode": {"bytes_in": pixel_bytes, "bytes_out": file_bytes},
                "file_write": {"bytes_in": file_bytes, "bytes_out": file_bytes},
            }
            write_ops_ms = {
                f"{op}_ms": round(seconds * 1000.0, 4)
                for op, seconds in zip(FILE_WRITE_OPS, write_op_seconds)
            }
        # the operations lie in the frame's last ``file_write`` stretch
        # (``write_image``'s; the one before ``encode`` found the path)
        written_in = max(
            (i for i, timed in enumerate(timing.steps) if timed[0] == "file_write"), default=None
        )
        # (a step outside obs.CPU_TIMED_STEPS has None for its CPU seconds,
        # and one handed over as the three of before PR 54 has none at all:
        # either is counted and drawn without them)
        for index, (name, start_wall, seconds, *cpu) in enumerate(timing.steps):
            cpu_seconds = cpu[0] if cpu else None
            if self._metrics is not None:
                self._step_histogram.observe(seconds, step=name)
                if cpu_seconds is not None:
                    self._step_cpu_seconds.inc(cpu_seconds, step=name)
            if self._span_tracer is not None:
                args = {"frame": frame.frame_index}
                if cpu_seconds is not None:
                    args["cpu_s"] = round(cpu_seconds, 6)
                args.update(step_bytes.get(name, {}))
                if name == "dispatch" and timing.kernel is not None:
                    args["kernel"] = timing.kernel
                if index == written_in:
                    args.update(write_ops_ms)
                self._span_tracer.complete(
                    name,
                    cat="worker.step",
                    start_wall=start_wall,
                    duration=seconds,
                    track=save_steps_track if name in SAVE_STEPS else steps_track,
                    args=args,
                )
        self._note_process_cpu()
        if self._metrics is not None:
            self._metrics.counter(
                "worker_frames_rendered_total", "Frames rendered successfully"
            ).inc()

    def _remove(self, frame: QueuedFrame) -> None:
        if frame in self._frames:
            self._frames.remove(frame)
