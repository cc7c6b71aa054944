"""The `tpu-raytrace` render backend: pure-JAX path tracing on TPU.

Drop-in replacement for the Blender subprocess backend behind the same
``RenderBackend`` interface — it emits the identical 7-phase
``FrameRenderTime`` so traces and the analysis suite cannot tell the
backends apart (BASELINE.md north star). Phase mapping:

- started_process/finished_loading: scene + camera build (host->device);
- started/finished_rendering: device compute (block_until_ready fenced);
- file_saving: PNG/JPEG encode + write;
- exited_process: after the output file hits disk.

A frame is two stages (``RenderBackend``): the **device stage**
(``resolve``, ``dispatch``, ``device_wait``, ``readback``: the u8 pixels
are on the host) and the **save stage** (``encode``, ``file_write``), and
the device stage parts into **issue** (``resolve``, ``dispatch``: nothing
is waited for) and **collect** (``device_wait``, ``readback``). Each is a
blocking call on a thread the event loop does not run on, so heartbeats
and queue RPCs stay responsive; the worker's queue issues frame *i+2*
while frame *i+1* is waited for and frame *i* is saved, so the device
finds the next frame's program in its queue when one ends, and the seven
points of consecutive frames overlap: frame *i*'s saving and the end of
its rendering lie inside frame *i+1*'s rendering. No buffer is donated:
frames issued back to back write the files of frames rendered one by one.

A frame's program is picked per JOB: ``(scene family, width, height,
samples, max_bounces)``, the shape from the job's ``[render]`` table over
the worker's flags (``program_key``). What a key needs (geometry, program,
first execute) is made resident once a process by ``prepare``: before
connecting (``--warmScene``), when a job is announced (``prepare_job``, on
a thread of its own while the render thread goes on with other jobs'
frames), or, where nobody announced the job, by its first frame as it
always was. A key is claimed under a lock and built by exactly one
thread; a frame that meets a preparation in hand waits for it.
"""

from __future__ import annotations

import asyncio
import functools
import threading
import time
from pathlib import Path

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.traces.worker_trace import FrameRenderTime
from tpu_render_cluster.utils.paths import parse_with_base_directory_prefix
from tpu_render_cluster.worker.backends.base import (
    IssuedFrame,
    RenderBackend,
    RenderedFrame,
)


# Linear bucket bounds for render_launch_occupancy: fractions live in
# [0, 1], where the default log ladder (1e-4..1e3) has almost no
# resolution.
ALIVE_FRACTION_BUCKETS = tuple((i + 1) / 16 for i in range(16))


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
    ) -> None:
        from tpu_render_cluster.obs.startup import get_startup, watch_jax_compiles
        from tpu_render_cluster.utils.accelerator import require_tpu_device

        # From here on every program JAX builds is counted by phase and,
        # from 10 ms, a span of the worker's timeline.
        watch_jax_compiles()
        # Refuses to build on a non-TPU backend unless JAX_PLATFORMS asks
        # for the CPU; the stamp rides the worker's exported metrics
        # snapshot.
        with get_startup().child("open_device"):
            self.device = require_tpu_device()
        self.base_directory = Path(base_directory) if base_directory else None
        # The worker's own shape: what a job whose [render] table is
        # silent (or absent) renders at.
        self.default_shape = (width, height, samples, max_bounces)
        self.tile_size = tile_size
        # None = single device; "tile" / "spp" shard a whole frame across
        # the local mesh (tpu_render_cluster/parallel/sharded_render.py).
        self.sharding = sharding
        # Every unit shape is exposed from the start, at 0: a scrape that
        # finds no series could not tell "no frame went that way" from
        # "not counted".
        self._tier_frames = self._tier_frames_counter()
        for tier in ("masked", "region", "sharded"):
            self._tier_frames.inc(0.0, tier=tier)
        for by in ("sort", "gather"):
            self._repacks_counter().inc(0.0, by=by)
        from tpu_render_cluster.obs import get_registry
        from tpu_render_cluster.render.integrator import TRACE_KERNELS

        self._kernel_frames = self._kernel_frames_counter()
        for kernel in TRACE_KERNELS:
            self._kernel_frames.inc(0.0, kernel=kernel)
        # "family@WxHxSxB tier" -> the trace kernel that program holds,
        # for every program this backend has built or fetched: the
        # worker's exit snapshot carries it
        self.trace_kernels: dict[str, str] = {}

        # program key -> set once what the key needs is resident. A key
        # is claimed by whoever meets it first (an announcement's thread,
        # --warmScene, or a frame) and built by that thread alone.
        self._resident: dict[tuple, threading.Event] = {}
        self._resident_lock = threading.Lock()
        # family -> bytes of its BLAS tables by memory space
        self._geometry: dict[str, dict[str, int]] = {}
        self._blas_units: dict[str, int] = {}
        self._last_key: tuple | None = None  # the render thread's own
        registry = get_registry()
        self._before_ready = registry.counter(
            "worker_frames_before_ready_total",
            "Frames that reached the render thread before what their job "
            "needs was resident, and waited for the preparation in hand (or "
            "built it themselves, where nobody had announced the job)",
        )
        self._switches = registry.counter(
            "worker_program_switches_total",
            "Frames whose program (scene family and shape) differs from "
            "the frame before on the render thread",
        )
        self._family_frames = registry.counter(
            "worker_frames_rendered_by_family_total",
            "Frames rendered and written, by scene family",
            labels=("family",),
        )
        for counter in (self._before_ready, self._switches):
            counter.inc(0.0)
        self._prepare_seconds = registry.histogram(
            "worker_job_prepare_seconds",
            "Seconds from a job's announcement (or --warmScene) to what "
            "its frames need being resident: geometry, program, first "
            "execute; near 0 where it already was",
            labels=("family",),
        )
        self._resident_programs = registry.gauge(
            "render_resident_program_units",
            "Frame programs resident in this process, one a (scene family, "
            "shape) that was prepared or rendered",
        )
        self._resident_programs.set(0.0)
        registry.gauge(
            "render_device_units",
            "Devices this backend renders on, labelled with JAX's platform "
            "and device_kind: what a reader needs to pick the chip's "
            "published peaks",
            labels=("platform", "kind"),
        ).set(
            float(self.device["count"]), platform=self.device["platform"],
            kind=self.device["device_kind"],
        )

    def warm(self, scene_name: str) -> None:
        """``prepare`` at the worker's own shape, before connecting: the
        process-level analog of pre-pulling the Blender container
        (reference: pull-blender-image.sh). The first XLA compile costs
        20-40 s and must not land inside a rendered frame's trace."""
        self.prepare(scene_name)

    def program_key(self, job: BlenderJob) -> tuple:
        """(scene family, width, height, samples, max_bounces) of the
        job's frames: its [render] table over this worker's flags."""
        from tpu_render_cluster.render.scene import scene_for_job_name

        shape = self.default_shape
        if job.render is not None:
            shape = job.render.shape(shape)
        return (scene_for_job_name(job.job_name), *shape)

    async def prepare_job(self, job: BlenderJob) -> None:
        await asyncio.to_thread(self.prepare, job.job_name, self.program_key(job))

    def _claim(self, key: tuple) -> tuple[threading.Event, bool]:
        """The key's residency event, and whether the caller is the one
        to build what it needs."""
        with self._resident_lock:
            done = self._resident.get(key)
            if done is not None:
                return done, False
            done = self._resident[key] = threading.Event()
            return done, True

    def _settle(self, key: tuple, done: threading.Event, built: bool) -> None:
        """End a claim: resident (the gauges say so), or given up, so
        that the next to meet the key builds it."""
        with self._resident_lock:
            if built:
                self._note_geometry(key[0])
            else:
                self._resident.pop(key, None)
            done.set()
            self._resident_programs.set(
                float(sum(event.is_set() for event in self._resident.values()))
            )

    def prepare(self, scene_name: str, key: tuple | None = None) -> None:
        """Make resident what frames of ``key`` need: geometry, the
        frame's program up to its first call's return (the executable
        exists, the work is queued), and one execute. Accepts job names as
        well as scene names, resolving exactly like the render path does —
        otherwise the prepared program can differ from the one the job
        compiles. Once a key: a second call, or one that meets a
        preparation in hand, waits for it and builds nothing.

        Before connecting it fills three of start-up's stages
        (obs/startup.py): ``geometry``, ``program_build``,
        ``first_execute``; after, what it spent is credited to them while
        the first frame is still awaited. Either way one ``job_prepare``
        span with the three as children, and one observation of
        ``worker_job_prepare_seconds{family}``.
        """
        import numpy as np

        from tpu_render_cluster.obs.startup import get_startup
        from tpu_render_cluster.render.scene import scene_for_job_name

        scene_name = scene_for_job_name(scene_name)
        if key is None:
            key = (scene_name, *self.default_shape)
        startup = get_startup()
        began = time.time()
        done, mine = self._claim(key)
        edges, staged = [began], False
        if not mine:
            done.wait()
        else:
            try:
                # a stage is entered before connecting only; afterwards
                # the mark is refused and the seconds are credited below
                staged = startup.enter("geometry")
                self._build_geometry(scene_name)
                edges.append(time.time())
                startup.enter("program_build")
                display = self._first_call(key)
                edges.append(time.time())
                startup.enter("first_execute")
                np.asarray(display)
                edges.append(time.time())
            except BaseException:
                self._settle(key, done, built=False)
                raise
            self._settle(key, done, built=True)
        seconds = time.time() - began
        track = "prepare {}@{}x{}x{}x{}".format(*key)
        for name, start, end in zip(
            ("geometry", "program_build", "first_execute"), edges, edges[1:]
        ):
            if not staged:
                startup.credit(name, end - start)
            startup.span(
                name, cat="worker.prepare", start_wall=start,
                duration=end - start, track=track,
            )
        startup.span(
            "job_prepare", cat="worker.prepare", start_wall=began,
            duration=seconds, track=track,
            args={
                "family": scene_name, "resident": not mine,
                "shape": "{}x{}x{}x{}".format(*key[1:]),
            },
        )
        self._prepare_seconds.observe(seconds, family=scene_name)

    def _first_call(self, key: tuple):
        """The first call of the key's whole-frame program: builds it,
        its ``render.compile`` spans named with the trace kernel it holds."""
        from tpu_render_cluster.obs.startup import compile_span_args

        scene_name, *shape = key
        sharded = self.sharding in ("tile", "spp")
        kernel = self._trace_kernel(key, "sharded" if sharded else "masked")
        with compile_span_args(kernel=kernel):
            if sharded:
                from tpu_render_cluster.parallel.sharded_render import sharded_frame_renderer

                return sharded_frame_renderer(scene_name, *shape, self.sharding)(1)
            from tpu_render_cluster.render.integrator import fused_frame_renderer

            # The program _render_pixels runs: with the live counts.
            display, *_ = fused_frame_renderer(scene_name, *shape, with_live=True)(1)
            return display

    def _trace_kernel(self, key: tuple, tier: str) -> str:
        """Which of ``integrator.TRACE_KERNELS`` the key's program of that
        unit shape holds: asked of the name function ``trace_paths``
        itself dispatches by, once a program."""
        from tpu_render_cluster.render.integrator import scene_trace_kernel

        program = "{}@{}x{}x{}x{} {}".format(*key, tier)
        kernel = self.trace_kernels.get(program)
        if kernel is None:
            # (an announcement's thread and a frame that meet a program at
            # once both ask, and store the same name)
            kernel = self.trace_kernels[program] = scene_trace_kernel(
                key[0], region=tier == "region"
            )
        return kernel

    def _build_geometry(self, scene_name: str) -> None:
        """Build the scene's BLAS or its set of BLASes (once a process:
        the renderer factories find them cached) and say how long each
        model took."""
        from tpu_render_cluster.obs import get_registry
        from tpu_render_cluster.obs.startup import get_startup
        from tpu_render_cluster.render import mesh
        from tpu_render_cluster.render.integrator import resolve_bvh_config
        from tpu_render_cluster.render.scene import mesh_kind_for_scene

        kind = mesh_kind_for_scene(scene_name)
        if kind is None:
            return
        seconds = get_registry().gauge(
            "render_bvh_build_seconds",
            "Seconds spent building a model's BLAS (model \"upload\": "
            "joining a set's tables and putting them on the device; one "
            "BLAS alone: its build and its copy together)",
            labels=("model",),
        )

        def built(model: str, triangles: int, began: float, took: float) -> None:
            # one gauge and one span of the worker's timeline a model
            seconds.set(took, model=model)
            get_startup().span(
                "bvh_build", cat="render", start_wall=began, duration=took,
                args={"model": model, "triangles": triangles},
            )

        mesh.cached_mesh_bvh(kind, *resolve_bvh_config()[2:], built=built)

    def _note_geometry(self, scene_name: str) -> None:
        """Say what this process holds now that ``scene_name`` is
        resident: its BLAS tables by family and memory space, and over
        every family the process has met (a worker that serves two holds
        both). A lookup: the build is cached."""
        from tpu_render_cluster.obs import get_registry
        from tpu_render_cluster.render import mesh
        from tpu_render_cluster.render.integrator import resolve_bvh_config
        from tpu_render_cluster.render.scene import mesh_kind_for_scene

        kind = mesh_kind_for_scene(scene_name)
        if scene_name in self._geometry:
            return
        if kind is None:
            self._geometry[scene_name] = {"hbm": 0, "vmem": 0, "smem": 0}
            self._blas_units[scene_name] = 0
        else:
            bvh = mesh.cached_mesh_bvh(kind, *resolve_bvh_config()[2:])
            self._geometry[scene_name] = mesh.geometry_bytes(bvh)
            self._blas_units[scene_name] = mesh.blas_count(bvh)
        registry = get_registry()
        by_family = registry.gauge(
            "render_resident_geometry_bytes",
            "Bytes of BLAS tables resident in this process, by scene family "
            "and by the memory they live in while a bounce kernel runs",
            labels=("family", "space"),
        )
        for space, count in self._geometry[scene_name].items():
            by_family.set(float(count), family=scene_name, space=space)
        if not any(self._blas_units.values()):
            return  # sphere families only: the mesh gauges stay unexposed
        registry.gauge(
            "render_geometry_blas_units",
            "BLASes the resident geometry holds: 1 a mesh family, or the "
            "models of a set; summed over the families this process holds",
        ).set(float(sum(self._blas_units.values())))
        where = registry.gauge(
            "render_geometry_bytes",
            "Bytes of the resident BLAS tables (a set's together, every "
            "family this process holds) by the memory they live in while a "
            "bounce kernel runs: hbm (streamed by treelet), vmem and smem "
            "(resident)",
            labels=("space",),
        )
        for space in ("hbm", "vmem", "smem"):
            where.set(
                float(sum(sizes[space] for sizes in self._geometry.values())),
                space=space,
            )

    async def render_frame(
        self, job: BlenderJob, frame_index: int, tile: int | None = None
    ) -> FrameRenderTime:
        return await asyncio.to_thread(self._render_sync, job, frame_index, tile)

    @staticmethod
    def _tier_frames_counter():
        from tpu_render_cluster.obs import get_registry

        return get_registry().counter(
            "render_tier_frames_total",
            "Frames rendered, by the shape of the work unit: masked (a "
            "whole frame on one device), region (a tile), sharded (a whole "
            "frame across the local mesh)",
            labels=("tier",),
        )

    @staticmethod
    def _kernel_frames_counter():
        from tpu_render_cluster.obs import get_registry

        return get_registry().counter(
            "render_trace_kernel_frames_total",
            "Frames rendered, by the trace kernel their program holds "
            "(integrator.trace_kernel_name, which trace_paths dispatches "
            "by): sphere_fused, mesh_fused (the two megakernels), "
            "mesh_bounce, mesh_stream (one bounce kernel a bounce, the BLAS "
            "resident or streamed from HBM), xla_loop (Pallas off)",
            labels=("kernel",),
        )

    # The three launch series keep the names the benchmark's per-layer
    # metrics (launch occupancy, pool_live_lane_share) read them by:
    # renaming a series is a change of yardstick.
    @staticmethod
    def _launch_occupancy_histogram():
        from tpu_render_cluster.obs import get_registry

        return get_registry().histogram(
            "render_launch_occupancy",
            "Per bounce launch of a deep mesh frame: live rays / the width "
            "the program ran the launch at",
            buckets=ALIVE_FRACTION_BUCKETS,
        )

    @staticmethod
    def _launched_lanes_counter():
        from tpu_render_cluster.obs import get_registry

        return get_registry().counter(
            "render_pool_launched_lanes_total",
            "Lanes launched: the width the program ran each bounce launch "
            "of a deep mesh frame at, summed over launches",
        )

    @staticmethod
    def _live_lanes_counter():
        from tpu_render_cluster.obs import get_registry

        return get_registry().counter(
            "render_pool_live_lanes_total",
            "Live rays at each bounce launch of a deep mesh frame, summed "
            "over launches",
        )

    @staticmethod
    def _repacks_counter():
        from tpu_render_cluster.obs import get_registry

        return get_registry().counter(
            "render_bounce_repacks_total",
            "Bounce launches of deep mesh frames, by how their rays were "
            "put in the launch's order: sort (the widest rung: the rays' "
            "state rides sorts on the order's inverse) or gather (a narrower "
            "rung: the travelling state gathered through the key order)",
            labels=("by",),
        )

    @classmethod
    def _observe_launches(cls, launches) -> None:
        """Launch occupancy of a whole frame of a deep mesh scene: every
        bounce is one kernel launch, ``launches[b]`` its (live rays,
        width) — the width the program picked for that bounce from its
        live count (integrator.launch_width_ladder), dead lanes sorted to
        the tail and skipped by blocks. The first bounce runs at the
        widest rung, and a launch at that width got its rays by a sort."""
        occupancy = cls._launch_occupancy_histogram()
        for live, width in launches:
            occupancy.observe(int(live) / int(width))
        cls._launched_lanes_counter().inc(float(launches[:, 1].sum()))
        cls._live_lanes_counter().inc(float(launches[:, 0].sum()))
        by_sort = int((launches[:, 1] == launches[0, 1]).sum())
        repacks = cls._repacks_counter()
        repacks.inc(float(by_sort), by="sort")
        repacks.inc(float(len(launches) - by_sort), by="gather")

    @staticmethod
    def _observe_walk(walk, scene_name: str) -> None:
        """The walk of a frame whose BLAS is streamed from HBM:
        ``walk[b]`` = bounce b's launch's counts in the order of
        ``pallas_kernels.WALK_COUNTS``, counted by the kernel and returned
        by the frame's program. Steps that are none of leaf tests, treelet
        entries and group tests are the resident top's."""
        from tpu_render_cluster.obs import get_registry
        from tpu_render_cluster.render.integrator import resolve_bvh_config
        from tpu_render_cluster.render.mesh import scene_blas_stream, treelet_fetch_bytes

        # the tables the frame's program was handed (cached: a lookup)
        fetch_bytes = treelet_fetch_bytes(
            scene_blas_stream(scene_name, *resolve_bvh_config()[2:])
        )
        registry = get_registry()
        fetches = float(walk[:, 1].sum())
        registry.counter(
            "render_walk_node_visits_total",
            "Steps the bounce launches' packets paid in the BLAS: box tests "
            "(a node of the top, or a wide node's eight children at once) "
            "and leaves' triangle tests (a step serves a whole block of rays)",
        ).inc(float(walk[:, 0].sum()))
        registry.counter(
            "render_walk_leaf_tests_total",
            "Of those steps, the leaves whose triangles were tested",
        ).inc(float(walk[:, 2].sum()))
        registry.counter(
            "render_walk_treelet_entries_total",
            "Of those steps, the wide tests of a treelet's root: a packet "
            "entered the treelet",
        ).inc(float(walk[:, 3].sum()))
        registry.counter(
            "render_walk_group_tests_total",
            "Of those steps, the wide tests of a group's eight leaves",
        ).inc(float(walk[:, 4].sum()))
        registry.counter(
            "render_treelet_fetches_total",
            "Treelets copied from HBM into a bounce kernel's scratch",
        ).inc(fetches)
        registry.counter(
            "render_treelet_prefetches_total",
            "Of those copies, the ones started while the packet still had "
            "another treelet of the same walk to walk (the rest are a walk's "
            "first)",
        ).inc(float(walk[:, 5].sum()))
        registry.counter(
            "render_treelet_fetch_bytes_total",
            "Bytes of treelet rows and wide nodes copied from HBM into a "
            "bounce kernel's scratch",
        ).inc(fetches * fetch_bytes)

    def _render_sync(
        self, job: BlenderJob, frame_index: int, tile: int | None = None
    ) -> FrameRenderTime:
        """Issue, collect and save back to back on the calling thread: a
        whole frame, for a caller with no other frame to run beside it."""
        return self.issue_device_stage(job, frame_index, tile).collect().save()

    def issue_device_stage(
        self, job: BlenderJob, frame_index: int, tile: int | None = None
    ) -> IssuedFrame:
        """The frame's ``resolve`` and ``dispatch``: its program fetched
        and called, the copies asked for, nothing waited for. Its
        exclusive steps (obs.step) ride on to ``collect`` and the save
        stage, each of which adds its own thread's; all of them are handed
        over beside the seven points."""
        from tpu_render_cluster.obs import frame_steps, step

        key = self.program_key(job)
        if self._last_key is not None and key != self._last_key:
            self._switches.inc()
        self._last_key = key
        done, mine = self._claim(key)
        with frame_steps() as steps:
            if not done.is_set():
                # Nobody announced the job (this frame builds what it
                # needs, as a first frame always did), or its preparation
                # is still in hand: wait for that one, never build twice.
                self._before_ready.inc()
                if not mine:
                    with step("resolve"):
                        done.wait()
            try:
                issued = self._issue_pixels(job, frame_index, tile, steps, key)
            except BaseException:
                if mine:
                    self._settle(key, done, built=False)
                raise
        if mine:
            # the executable exists and its first execute is in the
            # device's queue: whatever is issued next runs behind it
            self._settle(key, done, built=True)
        return issued

    def _issue_pixels(
        self, job: BlenderJob, frame_index: int, tile: int | None,
        steps: list[tuple[str, float, float, float | None]], key: tuple,
    ) -> IssuedFrame:
        import jax.numpy as jnp

        from tpu_render_cluster.obs import step
        from tpu_render_cluster.obs.startup import compile_span_args
        from tpu_render_cluster.render.integrator import (
            fused_frame_renderer,
            fused_region_renderer,
            tonemap,
        )
        started_process_at = time.time()

        # "Loading" = fetching (or first-building) the compiled renderer
        # for this scene/config — the analog of Blender's .blend load
        # phase. Scene construction itself is fused into the XLA program:
        # one device dispatch per frame instead of dozens of eager array
        # ops. There is one way to render; what the unit is decides the
        # program's shape. ``render()`` returns the u8 pixels and, for a
        # whole frame of a deep mesh scene, the per-bounce (live rays,
        # launch width) — an output of the frame's own program.
        with step("resolve"):
            scene_name, *shape = key
            width, height, samples, max_bounces = shape
            region = None
            if tile is not None:
                from tpu_render_cluster.jobs.tiles import tile_bounds

                if job.tile_grid is None:
                    raise RuntimeError(
                        f"Tile {tile} requested but job {job.job_name!r} "
                        "carries no tile grid."
                    )
                region = tile_bounds(tile, job.tile_grid, width=width, height=height)
            if region is not None:
                # A tile unit: the jitted region program (one compile per
                # tile shape; y0/x0/frame are traced) traces the FULL
                # frame's rays/RNG restricted to these pixels, so a
                # master-assembled grid of tiles is pixel-identical to the
                # whole frame (render/integrator.region_rays_and_seed).
                # Local sharding is bypassed: the unit is already
                # sub-frame work.
                tier = "region"
                y0, x0, tile_height, tile_width = region
                region_renderer = fused_region_renderer(
                    scene_name, width, height, tile_height, tile_width,
                    samples, max_bounces,
                )

                def render():
                    linear = region_renderer(
                        jnp.asarray(frame_index, jnp.float32), y0, x0
                    )
                    return tonemap(linear), None
            elif self.sharding in ("tile", "spp"):
                from tpu_render_cluster.parallel.sharded_render import sharded_frame_renderer

                tier = "sharded"
                sharded_renderer = sharded_frame_renderer(
                    scene_name, *shape, self.sharding
                )

                def render():
                    return tonemap(sharded_renderer(frame_index)), None
            else:
                tier = "masked"
                frame_renderer = fused_frame_renderer(
                    scene_name, *shape, with_live=True
                )

                def render():
                    return frame_renderer(frame_index)
            kernel = self._trace_kernel(key, tier)
        finished_loading_at = time.time()

        started_rendering_at = time.time()
        with step("dispatch"), compile_span_args(kernel=kernel):
            # a frame whose BLAS is streamed also returns its walk's counts
            # (a program first built here, inside a job, is a named span)
            display, launches, *walk = render()
            for counts in (launches, *walk):
                if counts is not None:
                    counts.copy_to_host_async()
            # Ask for the pixels now, behind the frame's work in the
            # device's queue, as np.asarray on an unfinished array does:
            # a copy first asked for after the wait below would cost the
            # frame a second host round trip.
            display.copy_to_host_async()
        # The device has this frame's work: nothing here waits for it, so
        # the next frame can be issued behind it and the frame before may
        # encode and write.
        return IssuedFrame(
            collect=functools.partial(
                self._collect_pixels, job, frame_index, tile, display, launches, walk,
                points=(started_process_at, finished_loading_at, started_rendering_at),
                issue_steps=steps, tier=tier, scene_name=scene_name,
                kernel=kernel,
            )
        )

    def _collect_pixels(
        self, job: BlenderJob, frame_index: int, tile: int | None,
        display, launches, walk: list, *,
        points: tuple[float, float, float],
        issue_steps: list[tuple[str, float, float, float | None]],
        tier: str, scene_name: str, kernel: str,
    ) -> RenderedFrame:
        """The rest of an issued frame's device stage, on whichever thread
        the caller runs it: one device sync, then (what is left of) the
        copy. Readback counts as rendering, like Blender's in-process
        compositing; "saving" is encode + disk only. A frame issued behind
        another waits here for both."""
        import numpy as np

        from tpu_render_cluster.obs import frame_steps, step

        with frame_steps() as collect_steps:
            with step("device_wait"):
                display.block_until_ready()
            with step("readback"):
                pixels = np.asarray(display)
                if launches is not None:
                    launches = np.asarray(launches)
                walk = [np.asarray(counts) for counts in walk]
        finished_rendering_at = time.time()

        return RenderedFrame(
            save=functools.partial(
                self._save_stage, job, frame_index, tile, pixels,
                points=(*points, finished_rendering_at),
                device_steps=[*issue_steps, *collect_steps], tier=tier,
                scene_name=scene_name, launches=launches, walk=walk,
                kernel=kernel,
            )
        )

    def _save_stage(
        self, job: BlenderJob, frame_index: int, tile: int | None, pixels, *,
        points: tuple[float, float, float, float],
        device_steps: list[tuple[str, float, float, float | None]],
        tier: str, scene_name: str, launches, walk: list, kernel: str,
    ) -> FrameRenderTime:
        """The frame's file, from its pixels: on whichever thread the
        caller runs it, with that thread's own steps. What the frame
        counts (tier, trace kernel, launches, walk, family) is counted
        once its file is in place, as it always was."""
        from tpu_render_cluster.obs import frame_steps, step
        from tpu_render_cluster.render.image_io import (
            output_path_for_frame,
            output_path_for_tile,
            write_image,
        )

        file_saving_started_at = time.time()
        with frame_steps() as save_steps:
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
            saved = write_image(
                path, pixels, "PNG" if tile is not None else job.output_file_format
            )
        file_saving_finished_at = time.time()

        self._tier_frames.inc(tier=tier)
        self._kernel_frames.inc(kernel=kernel)
        if launches is not None:
            self._observe_launches(launches)
        for counts in walk:
            self._observe_walk(counts, scene_name)
        self._family_frames.inc(family=scene_name)
        return FrameRenderTime(
            *points,
            file_saving_started_at=file_saving_started_at,
            file_saving_finished_at=file_saving_finished_at,
            exited_process_at=time.time(),
            steps=(*device_steps, *save_steps),
            saved=saved,
            kernel=kernel,
        )
