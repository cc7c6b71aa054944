"""Render backend interface."""

from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Callable

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.traces.worker_trace import FrameRenderTime


@dataclass(frozen=True)
class RenderedFrame:
    """A frame whose device stage is done: its pixels are on the host.

    ``save`` is the frame's save stage (encode, temporary file, write,
    close, rename): a plain blocking call for the thread the worker's
    queue gives it, which returns the frame's seven points once the file
    is in place. The queue runs it beside the NEXT frames' device stages,
    and beside the saves of the frames ahead of it that have not ended:
    what a save touches besides its own frame is shared between threads.
    """

    save: Callable[[], FrameRenderTime]


@dataclass(frozen=True)
class IssuedFrame:
    """A frame whose device work has been issued and not waited for.

    ``collect`` is the rest of the frame's device stage (the wait for the
    device, the copy to the host): a plain blocking call for the thread
    the worker's queue gives it, which returns the frame's
    ``RenderedFrame``. The device runs what it was handed in the order it
    was handed it, so the queue collects in the order it issued.
    """

    collect: Callable[[], RenderedFrame]


class RenderBackend(abc.ABC):
    """Renders the frames of a job and reports 7-phase timing.

    A frame has two stages. The **device stage** ends with the pixels on
    the host; the **save stage** ends with the file renamed into place.
    The worker's queue asks for the device stage (``render_device_stage``)
    and, where the backend hands back a ``RenderedFrame``, runs that
    frame's save stage while the next frames are in their device stage:
    up to ``worker/queue.py::SAVE_FRAMES`` frames saving at once, each on
    a thread of its own, a second one only where a frame's pixels arrive
    while a save is under way. A backend with no separable save stage
    implements ``render_frame`` alone and is asked for one whole frame at
    a time.

    A backend that can also part the device stage into **issue** (hand
    the device its work, return at once) and **collect** (wait, copy back)
    defines ``issue_device_stage``; the queue then keeps up to two frames
    issued and not yet collected, so that the device starts frame *i+1*
    the moment frame *i* ends. One that leaves it ``None`` is never asked
    for a frame before the one in its device stage has returned.

    Implementations must write the output file to the job's resolved output
    directory and return a ``FrameRenderTime`` whose phases satisfy the
    performance reducer's monotonicity requirements
    (tpu_render_cluster/traces/performance.py).

    Tiled jobs: when the job carries a tile grid, ``render_frame`` is
    called once per ``(frame, tile)`` work unit with ``tile`` set — the
    backend renders only that tile's pixel region and writes the tile
    file (render/image_io.output_path_for_tile naming); the master stitches
    the frame. Backends that cannot render sub-frame regions (the
    Blender subprocess backend) must raise a clear error instead of
    silently rendering the whole frame under a tile's name.
    """

    async def prepare_job(self, job: BlenderJob) -> None:
        """Make resident what the job's frames need, off the render path
        (called when a scheduler service announces the job; the worker
        reports the job ready when this returns). Nothing, for a backend
        whose first frame costs what every frame costs."""

    @abc.abstractmethod
    async def render_frame(
        self, job: BlenderJob, frame_index: int, tile: int | None = None
    ) -> FrameRenderTime:
        """One whole frame: returns when its file is in place."""

    async def render_device_stage(
        self,
        job: BlenderJob,
        frame_index: int,
        tile: int | None = None,
        *,
        dispatched: Callable[[], None],
    ) -> RenderedFrame | FrameRenderTime:
        """The frame's device stage, or, from a backend that cannot part
        the two, the whole frame (a ``FrameRenderTime``: nothing is left
        to save). ``dispatched`` is called, from any thread, once the
        frame's device work has been issued: the frame before begins its
        save stage behind that call, so that encoding does not contend
        with the Python that feeds the device. The caller lets go itself
        when the stage returns or raises, so a stage that fails early
        need not call it."""
        dispatched()
        return await self.render_frame(job, frame_index, tile=tile)

    # ``issue_device_stage(job, frame_index, tile=None) -> IssuedFrame``:
    # the frame's device work handed to the device, nothing waited for. A
    # plain blocking call for the queue's issue thread (it may build what
    # the job needs), one frame at a time and in the queue's order; what
    # it raises is the frame's error. None: this backend cannot part the
    # two (a subprocess a frame, a sleep), and ``render_device_stage`` is
    # all it is asked for.
    issue_device_stage: Callable[..., IssuedFrame] | None = None
