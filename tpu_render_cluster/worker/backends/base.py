"""Render backend interface."""

from __future__ import annotations

import abc

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.traces.worker_trace import FrameRenderTime


class RenderBackend(abc.ABC):
    """Renders one frame of a job and reports 7-phase timing.

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
        ...
