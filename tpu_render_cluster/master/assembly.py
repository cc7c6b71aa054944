"""Master-side frame assembly: stitch finished tiles into frame images.

The tile-sharded pipeline (PR 7) makes the unit of distribution a
``(frame, tile)`` work unit: each worker renders its tile region and
writes ``<frame>.tile_rRcC.png`` next to where the whole frame would go.
The master's exactly-once ledger (``ClusterManagerState``) knows the
moment the LAST tile of a frame reaches FINISHED — that transition fires
exactly once per frame — and this service then scatters the tile images
into the frame buffer: reads the grid's tiles, concatenates rows/columns,
writes the final frame file, and removes the tile intermediates.

Design constraints:

- **Exactly once**: the scheduling hook is only reachable through
  ``ClusterManagerState.mark_frame_as_finished``'s one-shot frame-complete
  transition, so duplicate/late copies of the final tile can never
  stitch a frame twice.
- **Off the event loop**: stitching is file I/O over potentially-megabyte
  images; it runs in a thread (``asyncio.to_thread``) and the master's
  event handling never blocks on it. ``drain()`` awaits every scheduled
  stitch — the job is not complete until its frames exist on disk.
- **Mock-tolerant**: integration/chaos clusters run backends that render
  nothing (worker/backends/mock.py). A frame whose tile files are absent
  is counted assembled in the ledger (the bookkeeping — what the chaos
  invariants audit — is exact) and the image pass is skipped.
"""

from __future__ import annotations

import asyncio
import logging
import time
from pathlib import Path

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.master.state import ClusterManagerState
from tpu_render_cluster.utils.background import BackgroundTasks
from tpu_render_cluster.utils.paths import parse_with_base_directory_prefix

logger = logging.getLogger(__name__)


def assemble_frame_files(
    job: BlenderJob,
    frame_index: int,
    *,
    base_directory: str | Path | None = None,
) -> Path | None:
    """Stitch one frame's tile files into its final image (sync).

    Returns the written frame path, or None when no tile files exist
    (mock-backend clusters render no pixels — the ledger still counts the
    frame assembled). Raises when tiles exist but are inconsistent: a
    partially-written grid is a bug worth surfacing, not papering over.
    """
    import numpy as np
    from PIL import Image

    from tpu_render_cluster.render.image_io import (
        output_path_for_frame,
        output_path_for_tile,
        write_image,
    )

    assert job.tile_grid is not None
    rows, cols = job.tile_grid
    try:
        output_directory = parse_with_base_directory_prefix(
            job.output_directory_path, base_directory
        )
    except ValueError:
        # %BASE% with no base directory on this master: nothing was (or
        # could have been) written where we can see it — mock/synthetic
        # clusters land here; the "no-tiles" outcome keeps it visible.
        return None
    tile_paths = [
        output_path_for_tile(
            output_directory,
            job.output_file_name_format,
            job.output_file_format,
            frame_index,
            tile,
            job.tile_grid,
        )
        for tile in range(rows * cols)
    ]
    existing = [p.exists() for p in tile_paths]
    if not any(existing):
        return None
    if not all(existing):
        missing = [str(p) for p, e in zip(tile_paths, existing) if not e]
        raise FileNotFoundError(
            f"Frame {frame_index}: {len(missing)} of {rows * cols} tile "
            f"file(s) missing at assembly time: {missing[:4]}"
        )
    tiles = [np.asarray(Image.open(p).convert("RGB")) for p in tile_paths]
    bands = [
        np.concatenate(tiles[r * cols : (r + 1) * cols], axis=1)
        for r in range(rows)
    ]
    pixels = np.concatenate(bands, axis=0)
    frame_path = output_path_for_frame(
        output_directory,
        job.output_file_name_format,
        job.output_file_format,
        frame_index,
    )
    write_image(frame_path, pixels, job.output_file_format)
    for path in tile_paths:
        try:
            path.unlink()
        except OSError:  # a vanished intermediate is not worth failing over
            pass
    return frame_path


def cut_writes_counter(metrics):
    """``master_cut_writes_removed_total``: the scheduler service exposes
    it at 0 from its start."""
    return metrics.counter(
        "master_cut_writes_removed_total",
        "Temporary files of frame writes that a worker's death cut, removed "
        "from their job's directory before the job was reported finished",
    )


def remove_cut_writes(
    job: BlenderJob,
    frame_indices: list[int],
    *,
    base_directory: str | Path | None = None,
) -> int:
    """Remove what a write that was cut left of the given frames (sync).

    ``write_image`` writes ``.<file name>.<random>.tmp`` beside the frame
    and renames it into place; a worker killed in between leaves the
    temporary file. Only the named frames' (and their tiles') are touched:
    a live worker's write of another frame is under way in the same
    directory. Returns how many files went; 0 where this master cannot
    see the directory."""
    import os

    from tpu_render_cluster.render.image_io import (
        output_path_for_frame,
        output_path_for_tile,
    )

    try:
        output_directory = parse_with_base_directory_prefix(
            job.output_directory_path, base_directory
        )
    except ValueError:
        return 0  # %BASE% with no base directory on this master
    names = set()
    for frame_index in frame_indices:
        names.add(output_path_for_frame(
            output_directory, job.output_file_name_format, job.output_file_format, frame_index
        ).name)
        if job.tile_grid is not None:
            rows, cols = job.tile_grid
            names.update(
                output_path_for_tile(
                    output_directory, job.output_file_name_format,
                    job.output_file_format, frame_index, tile, job.tile_grid,
                ).name
                for tile in range(rows * cols)
            )
    removed = 0
    try:
        entries = list(os.scandir(output_directory))
    except OSError:
        return 0
    for entry in entries:
        name = entry.name
        if not (name.startswith(".") and name.endswith(".tmp")):
            continue
        if name[1:].rsplit(".", 2)[0] in names:
            try:
                os.unlink(entry.path)
                removed += 1
            except OSError:
                pass
    return removed


class FrameAssemblyService:
    """Schedules and tracks per-frame assembly on the master's loop.

    ``schedule`` is the sync hook WorkerHandle fires from the finished-
    event path (exactly once per frame); ``drain`` is the completion
    barrier the job/scheduler awaits before declaring a tiled job done:
    no stitch is pending when it returns (``has_pending`` is false), and
    it always gives the loop a turn (``utils/background.py``).
    """

    def __init__(
        self,
        *,
        metrics=None,
        span_tracer=None,
        base_directory: str | Path | None = None,
    ) -> None:
        self.metrics = metrics
        self.span_tracer = span_tracer
        self.base_directory = base_directory
        # Stitches in flight, keyed by owning job_name, so per-job
        # completion (the scheduler's finalize gate) can be answered
        # without touching other jobs' stitches.
        self._tasks = BackgroundTasks()

    def schedule(self, state: ClusterManagerState, frame_index: int) -> None:
        """All tiles of ``frame_index`` landed: stitch it in the background."""
        self._tasks.spawn(
            self._assemble(state, frame_index),
            name=f"assemble-{state.job.job_name}-{frame_index}",
            key=state.job.job_name,
        )

    def schedule_cut_write_sweep(
        self, state: ClusterManagerState, frame_indices: list[int]
    ) -> None:
        """A worker died holding these frames of the job: what a write it
        was cut in left beside the frames goes, in the background, and the
        job is not declared FINISHED before (``has_pending``)."""
        self._tasks.spawn(
            self._sweep_cut_writes(state.job, frame_indices),
            name=f"sweep-{state.job.job_name}",
            key=state.job.job_name,
        )

    async def _sweep_cut_writes(self, job: BlenderJob, frame_indices: list[int]) -> None:
        try:
            removed = await asyncio.to_thread(
                remove_cut_writes, job, frame_indices, base_directory=self.base_directory
            )
        except Exception as e:  # noqa: BLE001 - account, don't kill the loop
            logger.error("Sweep of cut writes of %r failed: %s", job.job_name, e)
            return
        if self.metrics is not None:
            cut_writes_counter(self.metrics).inc(removed)
        if removed:
            logger.warning(
                "Removed %d temporary file(s) a dead worker's cut write left in %r.",
                removed, job.job_name,
            )

    def has_pending(self, job_name: str) -> bool:
        """Stitches of ``job_name`` still in flight — a job must not be
        declared FINISHED (nor its name released for reuse) before they
        land."""
        return bool(self._tasks.pending(job_name))

    async def drain(self) -> None:
        """Await every scheduled assembly (the tiled-job completion barrier)."""
        await self._tasks.drain()

    async def drain_job(self, job_name: str) -> None:
        """Await one job's in-flight stitches (the cancel path: the job's
        name must not be released for reuse while its stitcher can still
        read/write/unlink files under the shared output path)."""
        await self._tasks.drain(job_name)

    async def _assemble(
        self, state: ClusterManagerState, frame_index: int
    ) -> None:
        started_wall = time.time()
        started = time.perf_counter()
        result = "ok"
        try:
            path = await asyncio.to_thread(
                assemble_frame_files,
                state.job,
                frame_index,
                base_directory=self.base_directory,
            )
        except Exception as e:  # noqa: BLE001 - account, don't kill the loop
            result = "errored"
            path = None
            logger.error(
                "Assembly of frame %d (%r) failed: %s",
                frame_index,
                state.job.job_name,
                e,
            )
        else:
            if path is None:
                result = "no-tiles"
        # The LEDGER transition is unconditional: the frame's tiles all
        # reached FINISHED exactly once, which is what the chaos
        # invariants audit; the image pass is reported separately.
        state.note_frame_assembled(frame_index)
        duration = time.perf_counter() - started
        if self.metrics is not None:
            self.metrics.counter(
                "master_frames_assembled_total",
                "Tiled frames whose tiles all landed, by stitch outcome",
                labels=("result",),
            ).inc(result=result)
            self.metrics.histogram(
                "master_frame_assembly_seconds",
                "Tile-stitch duration per assembled frame",
            ).observe(duration)
        if self.span_tracer is not None:
            self.span_tracer.complete(
                "frame assembled",
                cat="master",
                start_wall=started_wall,
                duration=duration,
                track="assembly",
                args={
                    "frame": frame_index,
                    "job": state.job.job_name,
                    "tiles": state.job.tiles_per_frame(),
                    "result": result,
                },
            )
