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
    is in place. The queue runs it beside the NEXT frame's device stage.
    """

    save: Callable[[], FrameRenderTime]


class RenderBackend(abc.ABC):
    """Renders the frames of a job and reports 7-phase timing.

    A frame has two stages. The **device stage** ends with the pixels on
    the host; the **save stage** ends with the file renamed into place.
    The worker's queue asks for the device stage (``render_device_stage``)
    and, where the backend hands back a ``RenderedFrame``, runs that
    frame's save stage while the next frame is in its device stage: up to
    two frames of one backend are in hand at a time, never two in the
    same stage. A backend with no separable save stage implements
    ``render_frame`` alone and is asked for one whole frame at a time.

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
