"""The `tpu-raytrace` render backend: pure-JAX path tracing on TPU.

Drop-in replacement for the Blender subprocess backend behind the same
``RenderBackend`` interface — it emits the identical 7-phase
``FrameRenderTime`` so traces and the analysis suite cannot tell the
backends apart (BASELINE.md north star). Phase mapping:

- started_process/finished_loading: scene + camera build (host->device);
- started/finished_rendering: device compute (block_until_ready fenced);
- file_saving: tonemap + PNG/JPEG encode + write;
- exited_process: after the output file hits disk.

The heavy work runs in a thread (`asyncio.to_thread`) so heartbeats and
queue RPCs stay responsive while a frame renders.
"""

from __future__ import annotations

import asyncio
import time
from pathlib import Path

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.traces.worker_trace import FrameRenderTime
from tpu_render_cluster.utils.paths import parse_with_base_directory_prefix
from tpu_render_cluster.worker.backends.base import RenderBackend


class TpuRaytraceBackend(RenderBackend):
    def __init__(
        self,
        *,
        base_directory: str | Path | None = None,
        width: int = 512,
        height: int = 512,
        samples: int = 8,
        max_bounces: int = 4,
        tile_size: int | None = None,
        sharding: str | None = None,
        wavefront: str | None = None,
        raypool: str | None = None,
    ) -> None:
        from tpu_render_cluster.utils.accelerator import require_tpu_device

        # Refuses to build on a non-TPU backend unless JAX_PLATFORMS asks
        # for the CPU; the stamp rides the worker's exported metrics
        # snapshot.
        self.device = require_tpu_device()
        self.base_directory = Path(base_directory) if base_directory else None
        self.width = width
        self.height = height
        self.samples = samples
        self.max_bounces = max_bounces
        self.tile_size = tile_size
        # None = single device; "tile" / "spp" shard across the local mesh
        # (tpu_render_cluster/parallel/sharded_render.py).
        self.sharding = sharding
        # Wavefront (compact + bucketed relaunch) execution: None defers
        # to the TRC_WAVEFRONT env tier; "off"/"auto"/"force" override it
        # per backend (render/compaction.py). Only "force" turns it on;
        # auto is the one-program tier for every scene. Only applies to
        # the single-device path — tile/spp sharding gets the IN-JIT
        # compaction (live-count tail skip) instead, which composes with
        # shard_map.
        self.wavefront = wavefront
        # Device-resident ray pool (render/raypool.py): None defers to the
        # TRC_RAYPOOL env tier; "off"/"auto"/"force" override per backend.
        # Only "force" turns it on (frames queued ahead do not). The
        # queue's note_upcoming_frames hint supplies the work-ahead, and
        # the backend then renders several of ITS OWN queued frames in
        # one pool batch, serving later requests from the cache below.
        # Worker-internal only: one frame per request on the wire.
        self.raypool = raypool
        # Work units (jobs.tiles.WorkUnit) of each job still queued here.
        self._upcoming: dict[str, tuple] = {}
        # (job_name, frame_index, tile) -> linear image rendered ahead by
        # a pool batch. Bounded BY BYTES: stale entries (stolen/removed
        # units we rendered ahead of) are evicted oldest-first.
        self._raypool_cache: dict[tuple[str, int, int | None], object] = {}
        # The three whole-frame tiers are exposed from the start, at 0: a
        # scrape that finds no series could not tell "no frame went that
        # way" from "not counted".
        self._tier_frames = self._tier_frames_counter()
        for tier in ("masked", "wavefront", "raypool"):
            self._tier_frames.inc(0.0, tier=tier)

    # Staleness backstop, not a working-set budget: live entries drain
    # within one pool window of requests, so anything pushing the cache
    # past this is stolen/removed frames.
    _RAYPOOL_CACHE_MAX_BYTES = 64 * 1024 * 1024

    def note_upcoming_frames(self, job: BlenderJob, units: tuple) -> None:
        """Queue hint (RenderBackend hint protocol): same-job work units
        still queued on this worker, i.e. what a pool batch may render
        ahead (same-tile units of other frames, for tiled jobs).

        An empty hint drops the job's entry — the map tracks only jobs
        with outstanding local work, so a long-lived worker's job history
        doesn't accumulate here. Bare ints are accepted as whole-frame
        units (the pre-tiling call shape).
        """
        if units:
            from tpu_render_cluster.jobs.tiles import WorkUnit

            self._upcoming[job.job_name] = tuple(
                WorkUnit(u) if isinstance(u, int) else u for u in units
            )
        else:
            self._upcoming.pop(job.job_name, None)

    def _use_wavefront(self, scene_name: str) -> bool:
        if self.sharding in ("tile", "spp"):
            return False
        from tpu_render_cluster.render.compaction import wavefront_active

        return wavefront_active(scene_name, backend_flag=self.wavefront)

    def _use_raypool(self, scene_name: str, frames_ahead: int) -> bool:
        if self.sharding in ("tile", "spp"):
            return False
        from tpu_render_cluster.render.raypool import raypool_active

        return raypool_active(
            scene_name,
            backend_flag=self.raypool,
            frames_ahead=frames_ahead,
        )

    def warm(self, scene_name: str) -> None:
        """Compile + execute the renderer once, outside any job window.

        The process-level analog of pre-pulling the Blender container
        (reference: pull-blender-image.sh): the first XLA compile costs
        20-40 s and must not land inside a rendered frame's trace.
        """
        import numpy as np

        from tpu_render_cluster.render.scene import scene_for_job_name

        # Accept job names as well as scene names, resolving exactly like
        # the render path does — otherwise the warmed program can differ
        # from the one the job compiles.
        scene_name = scene_for_job_name(scene_name)

        if self.sharding in ("tile", "spp"):
            from tpu_render_cluster.parallel.sharded_render import render_frame_sharded

            np.asarray(
                render_frame_sharded(
                    scene_name,
                    1,
                    width=self.width,
                    height=self.height,
                    samples=self.samples,
                    max_bounces=self.max_bounces,
                    mode=self.sharding,
                )
            )
            return
        if self._use_raypool(scene_name, frames_ahead=1):
            # The pool program is one compile per pool config, batch size
            # independent — a single-frame batch warms it completely. The
            # per-frame fallback below is ALSO warmed: the job's tail
            # frame (nothing queued behind it) renders through it, and
            # its compile must not land inside a frame trace either.
            from tpu_render_cluster.render.raypool import render_batch_raypool

            np.asarray(
                render_batch_raypool(
                    scene_name,
                    [1],
                    width=self.width,
                    height=self.height,
                    samples=self.samples,
                    max_bounces=self.max_bounces,
                )[0]
            )
        if self._use_wavefront(scene_name):
            # One full wavefront frame: compiles the compaction +
            # bounce programs for the buckets this workload actually
            # visits (render_compiles_total then stays flat over the
            # job's frames).
            from tpu_render_cluster.render.compaction import render_frame_wavefront

            np.asarray(
                render_frame_wavefront(
                    scene_name,
                    1,
                    width=self.width,
                    height=self.height,
                    samples=self.samples,
                    max_bounces=self.max_bounces,
                )
            )
        else:
            from tpu_render_cluster.render.integrator import fused_frame_renderer

            # The program _render_timed runs: with the live counts.
            display, _ = fused_frame_renderer(
                scene_name,
                self.width,
                self.height,
                self.samples,
                self.max_bounces,
                with_live=True,
            )(1)
            np.asarray(display)

    async def render_frame(
        self, job: BlenderJob, frame_index: int, tile: int | None = None
    ) -> FrameRenderTime:
        return await asyncio.to_thread(self._render_sync, job, frame_index, tile)

    def _trim_raypool_cache(self) -> None:
        """Evict oldest rendered-ahead frames past the byte cap (stale
        entries accumulate when frames we batched ahead get stolen or
        removed; at production resolution each image is megabytes, so the
        bound must be bytes, not entries)."""
        excess = (
            sum(
                getattr(image, "nbytes", 0)
                for image in self._raypool_cache.values()
            )
            - self._RAYPOOL_CACHE_MAX_BYTES
        )
        while self._raypool_cache and excess > 0:
            victim = self._raypool_cache.pop(next(iter(self._raypool_cache)))
            excess -= getattr(victim, "nbytes", 0)

    @staticmethod
    def _tier_frames_counter():
        from tpu_render_cluster.obs import get_registry

        return get_registry().counter(
            "render_tier_frames_total",
            "Frames rendered, by the execution tier that rendered them",
            labels=("tier",),
        )

    @staticmethod
    def _observe_launches(launches) -> None:
        """Launch occupancy of a one-program frame of a deep mesh scene:
        every bounce is one kernel launch, ``launches[b]`` its (live
        rays, width) — the width the program picked for that bounce from
        its live count (integrator.launch_width_ladder), dead lanes
        sorted to the tail and skipped by blocks. Fed into the series the
        wavefront driver (per relaunch, live / bucket) and the raypool
        (per iteration, live / launched lanes) feed for their launches,
        so the tier that renders is the one the occupancy describes."""
        from tpu_render_cluster.render.compaction import launch_occupancy_histogram
        from tpu_render_cluster.render.raypool import (
            pool_launched_lanes_counter,
            pool_live_lanes_counter,
        )

        occupancy = launch_occupancy_histogram()
        for live, width in launches:
            occupancy.observe(int(live) / int(width))
        pool_launched_lanes_counter().inc(float(launches[:, 1].sum()))
        pool_live_lanes_counter().inc(float(launches[:, 0].sum()))

    @staticmethod
    def _observe_render_obs(
        *, execute_seconds: float, from_cache: bool = False,
        kernel: str | None = None,
    ) -> None:
        """Feed the process-global obs registry (one TPU per process).

        The frame's own times are the phase and step histograms the
        worker queue feeds; what is left here is what those cannot say:
        cache hits, the frames/s gauge bench.py shares, and the roofline
        pairing.
        """
        from tpu_render_cluster.obs import get_registry, render_fps_gauge

        registry = get_registry()
        if from_cache:
            # A ray-pool cache hit: this frame's device time was amortized
            # into the batch that rendered it ahead — its ~tonemap-only
            # execute time does not belong in the fps gauge (it would
            # report fantasy per-frame device rates under batching).
            registry.counter(
                "render_raypool_cache_hits_total",
                "Frames served from the ray-pool rendered-ahead cache",
            ).inc()
            return
        if execute_seconds > 0:
            render_fps_gauge(registry).set(1.0 / execute_seconds)
        if kernel is not None and execute_seconds > 0:
            # Roofline pairing: this tier's whole frame is one fenced
            # program execution (render + readback), keyed identically to
            # the cost capture inside the renderer factory.
            from tpu_render_cluster.obs.profiling import get_profiler

            get_profiler().record_execute(kernel, execute_seconds)

    def _render_sync(
        self, job: BlenderJob, frame_index: int, tile: int | None = None
    ) -> FrameRenderTime:
        """One frame on the render thread; its exclusive steps (obs.step)
        ride the timing beside the seven points."""
        from tpu_render_cluster.obs import frame_steps

        with frame_steps() as steps:
            return self._render_timed(job, frame_index, tile, steps)

    def _render_timed(
        self, job: BlenderJob, frame_index: int, tile: int | None,
        steps: list[tuple[str, float, float]],
    ) -> FrameRenderTime:
        import numpy as np

        from tpu_render_cluster.obs import step
        from tpu_render_cluster.render.image_io import (
            output_path_for_frame,
            output_path_for_tile,
            write_image,
        )
        from tpu_render_cluster.render.integrator import fused_frame_renderer, tonemap
        from tpu_render_cluster.render.scene import scene_for_job_name

        started_process_at = time.time()

        with step("resolve"):
            scene_name = scene_for_job_name(job.job_name)
            # Tiled work unit: resolve the tile's pixel region once. All three
            # execution tiers below serve it through their region paths, which
            # trace the FULL frame's rays/RNG restricted to these pixels — a
            # master-assembled grid of tiles is pixel-identical to the
            # whole-frame render (render/integrator.region_rays_and_seed).
            region = None
            if tile is not None:
                from tpu_render_cluster.jobs.tiles import tile_bounds

                if job.tile_grid is None:
                    raise RuntimeError(
                        f"Tile {tile} requested but job {job.job_name!r} "
                        "carries no tile grid."
                    )
                region = tile_bounds(
                    tile, job.tile_grid, width=self.width, height=self.height
                )
            # "Loading" = fetching (or first-building) the compiled renderer for
            # this scene/config — the analog of Blender's .blend load phase.
            # Scene construction itself is fused into the XLA program: one
            # device dispatch per frame instead of dozens of eager array ops.
            # Wavefront mode has no single cached renderer (its per-bucket
            # programs compile lazily inside the render — warm() pre-visits
            # them), so its loading phase is just scene-name resolution; same
            # for the ray-pool path (one pool program per config, warmed).
            cache_key = (job.job_name, frame_index, tile)
            cached_linear = self._raypool_cache.pop(cache_key, None)
            # Work-ahead for a pool batch: same-job units still queued HERE
            # with the SAME tile (a pool batch spans frames, not regions).
            upcoming = [
                u.frame_index
                for u in self._upcoming.get(job.job_name, ())
                if u.tile == tile
                and u.frame_index != frame_index
                and (job.job_name, u.frame_index, tile) not in self._raypool_cache
            ]
            use_raypool = cached_linear is None and self._use_raypool(
                scene_name, frames_ahead=len(upcoming)
            )
            use_wavefront = (
                cached_linear is None
                and not use_raypool
                and self._use_wavefront(scene_name)
            )
            use_sharded = self.sharding in ("tile", "spp") and region is None
            # The tier that renders this frame (render_tier_frames_total's
            # label; a frame served from the rendered-ahead cache was
            # rendered by the pool).
            if cached_linear is not None or use_raypool:
                tier = "raypool"
            elif use_sharded:
                tier = "sharded"
            elif use_wavefront:
                tier = "wavefront"
            elif region is not None:
                tier = "region"
            else:
                tier = "masked"
                renderer = fused_frame_renderer(
                    scene_name,
                    self.width,
                    self.height,
                    self.samples,
                    self.max_bounces,
                    with_live=True,
                )
        finished_loading_at = time.time()

        started_rendering_at = time.time()
        # The one-program tier's per-bounce (live rays, launch width) (deep
        # mesh scenes only), an output of the frame's own program.
        launches = None
        # Issuing the device's work; the wavefront and raypool drivers open
        # their own device_wait / readback steps inside, which suspend it.
        with step("dispatch"):
            if cached_linear is not None:
                # Rendered ahead by an earlier pool batch of this job: only
                # the tonemap + readback run now. The batch's device time was
                # carried by the frame that triggered it — per-frame phase
                # timings under batching reflect that amortization.
                display = tonemap(cached_linear)
            elif use_sharded:
                from tpu_render_cluster.parallel.sharded_render import render_frame_sharded

                linear = render_frame_sharded(
                    scene_name,
                    frame_index,
                    width=self.width,
                    height=self.height,
                    samples=self.samples,
                    max_bounces=self.max_bounces,
                    mode=self.sharding,
                )
                display = tonemap(linear)
            elif use_raypool:
                from tpu_render_cluster.render.raypool import (
                    raypool_frame_cap,
                    render_batch_raypool,
                )

                # One pool window: this unit plus the next queued same-tile
                # frames of the same job (the queue's hint — all assigned to
                # THIS worker, so nothing is rendered speculatively). Units
                # rendered ahead are served from the cache on their own
                # requests.
                batch = [frame_index] + upcoming[: raypool_frame_cap() - 1]
                images = render_batch_raypool(
                    scene_name,
                    batch,
                    width=self.width,
                    height=self.height,
                    samples=self.samples,
                    max_bounces=self.max_bounces,
                    region=region,
                )
                for ahead_frame, image in zip(batch[1:], images[1:]):
                    self._raypool_cache[(job.job_name, ahead_frame, tile)] = image
                self._trim_raypool_cache()
                display = tonemap(images[0])
            elif use_wavefront:
                from tpu_render_cluster.render.compaction import (
                    render_frame_wavefront,
                    render_region_wavefront,
                )

                if region is None:
                    linear = render_frame_wavefront(
                        scene_name,
                        frame_index,
                        width=self.width,
                        height=self.height,
                        samples=self.samples,
                        max_bounces=self.max_bounces,
                    )
                else:
                    y0, x0, tile_height, tile_width = region
                    linear = render_region_wavefront(
                        scene_name,
                        frame_index,
                        y0=y0,
                        x0=x0,
                        tile_height=tile_height,
                        tile_width=tile_width,
                        width=self.width,
                        height=self.height,
                        samples=self.samples,
                        max_bounces=self.max_bounces,
                    )
                display = tonemap(linear)
            elif region is not None:
                # Masked tier, one tile: the jitted region program (one
                # compile per tile shape; y0/x0/frame are traced). Local
                # tile/spp sharding is bypassed for cluster-tile units — the
                # unit is already sub-frame work.
                from tpu_render_cluster.render.integrator import render_frame_region

                y0, x0, tile_height, tile_width = region
                linear = render_frame_region(
                    scene_name,
                    frame_index,
                    y0=y0,
                    x0=x0,
                    tile_height=tile_height,
                    tile_width=tile_width,
                    width=self.width,
                    height=self.height,
                    samples=self.samples,
                    max_bounces=self.max_bounces,
                )
                display = tonemap(linear)
            else:
                display, launches = renderer(frame_index)
                if launches is not None:
                    launches.copy_to_host_async()
            # Ask for the pixels now, behind the frame's work in the
            # device's queue, as np.asarray on an unfinished array does:
            # a copy first asked for after the wait below would cost the
            # frame a second host round trip.
            display.copy_to_host_async()
        # One device sync per frame, then (what is left of) the copy.
        # Readback counts as rendering, like Blender's in-process
        # compositing; "saving" below is encode + disk only.
        with step("device_wait"):
            display.block_until_ready()
        with step("readback"):
            pixels = np.asarray(display)
            if launches is not None:
                launches = np.asarray(launches)
        finished_rendering_at = time.time()

        file_saving_started_at = time.time()
        with step("file_write"):
            output_directory = parse_with_base_directory_prefix(
                job.output_directory_path, self.base_directory
            )
            if tile is None:
                path = output_path_for_frame(
                    output_directory,
                    job.output_file_name_format,
                    job.output_file_format,
                    frame_index,
                )
            else:
                # One tile file per unit; the master's assembly service
                # stitches the grid into the frame file and removes these.
                # Always PNG (lossless — see image_io.output_path_for_tile);
                # the assembler encodes the final frame in the job's format.
                path = output_path_for_tile(
                    output_directory,
                    job.output_file_name_format,
                    job.output_file_format,
                    frame_index,
                    tile,
                    job.tile_grid,
                )
        write_image(
            path, pixels, "PNG" if tile is not None else job.output_file_format
        )
        file_saving_finished_at = time.time()

        # Which roofline kernel this frame's fenced execute time pairs
        # with: only tiers whose frame is ONE program execution keyed by
        # a factory-side cost capture (the wavefront/raypool drivers pair
        # their own launches internally; cache hits executed nothing;
        # sharded programs are per-device and not cost-captured).
        kernel = None
        if tier in ("region", "masked"):
            from tpu_render_cluster.obs.profiling import kernel_key

            dims = dict(w=self.width, h=self.height, s=self.samples, b=self.max_bounces)
            if region is not None:
                dims.update(th=region[2], tw=region[3])
            kernel = kernel_key(tier, scene_name, **dims)
        self._tier_frames.inc(tier=tier)
        if launches is not None:
            self._observe_launches(launches)
        self._observe_render_obs(
            execute_seconds=finished_rendering_at - started_rendering_at,
            from_cache=cached_linear is not None,
            kernel=kernel,
        )
        return FrameRenderTime(
            started_process_at=started_process_at,
            finished_loading_at=finished_loading_at,
            started_rendering_at=started_rendering_at,
            finished_rendering_at=finished_rendering_at,
            file_saving_started_at=file_saving_started_at,
            file_saving_finished_at=file_saving_finished_at,
            exited_process_at=time.time(),
            steps=tuple(steps),
        )
