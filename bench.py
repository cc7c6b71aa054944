#!/usr/bin/env python
"""Headline benchmark: path-traced frames/sec/chip on the 04_very-simple scene.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "frames/s/chip", "vs_baseline": R}

``vs_baseline`` compares against the single-host CPU render of the same
workload (the stand-in for the reference's 1-worker eager-naive-coarse CPU
Blender baseline — BASELINE.md north star is >=8x). The CPU number is
measured in a subprocess with JAX_PLATFORMS=cpu unless BENCH_CPU_FPS is set
(the driver can pin it to keep runs short).

Workload: 256x256, 4 spp, 4 bounces — matching the 04_very-simple class of
trivially-lit scenes rendered at JPEG-preview quality in the reference runs.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time

WIDTH = 256
HEIGHT = 256
SAMPLES = 4
BOUNCES = 4
BATCH = 8  # frames per vmapped inner batch
CHUNKS = 128  # scan steps per dispatch -> CHUNKS*BATCH frames per dispatch
REPS = 5  # report the median of this many independent timed windows
MIN_WINDOW_S = 5.0  # each timed window covers at least this much device time

# Measurement methodology: each dispatch renders CHUNKS*BATCH frames
# inside one jitted lax.scan and returns per-chunk means (a few floats);
# fetching that tiny array to host forces real completion of every chunk.
# Windows of >= MIN_WINDOW_S are timed fetch-to-fetch (a short window is
# dominated by a one-time post-warmup dispatch hiccup), and the median
# over REPS windows is reported.


def _make_render_many(chunks: int, scene_name: str = "04_very-simple"):
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.integrator import render_tile
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    def render_one(frame):
        scene = build_scene(scene_name, frame)
        camera = scene_camera(scene_name, frame)
        return render_tile(
            scene,
            camera,
            frame,
            0,
            0,
            width=WIDTH,
            height=HEIGHT,
            tile_height=HEIGHT,
            tile_width=WIDTH,
            samples=SAMPLES,
            max_bounces=BOUNCES,
            mesh=scene_mesh_set(scene_name, frame),
        )

    @jax.jit
    def render_many(frame0):
        def body(carry, c):
            fr = frame0 + c * BATCH + jnp.arange(BATCH, dtype=jnp.float32)
            return carry, jax.vmap(render_one)(fr).mean()

        _, means = jax.lax.scan(
            body, 0.0, jnp.arange(chunks, dtype=jnp.float32)
        )
        return means

    return render_many


def measure_fps(
    reps: int = REPS,
    min_window_s: float = MIN_WINDOW_S,
    chunks: int = CHUNKS,
    scene_name: str = "04_very-simple",
) -> float:
    """Median frames/sec over ``reps`` fully-synced timed windows."""
    import statistics

    import jax

    render_many = _make_render_many(chunks, scene_name)
    per_dispatch = chunks * BATCH

    def timed_dispatch(frame0: float) -> float:
        t0 = time.perf_counter()
        jax.device_get(render_many(frame0))  # tiny fetch = real sync
        return time.perf_counter() - t0

    timed_dispatch(1.0)  # compile + warm caches
    if min_window_s > 0:
        timed_dispatch(1.0 + per_dispatch)  # absorb post-warmup hiccup

    fps = []
    offset = 1.0 + 2 * per_dispatch
    for _ in range(reps):
        # Accumulate dispatches until the window is long enough; a fixed
        # count derived from one probe could under-fill it if the probe
        # happened to be a slow outlier.
        frames_done = 0
        t0 = time.perf_counter()
        while True:
            jax.device_get(render_many(offset))
            offset += per_dispatch
            frames_done += per_dispatch
            elapsed = time.perf_counter() - t0
            if elapsed >= min_window_s:
                break
        fps.append(frames_done / elapsed)
    median_fps = statistics.median(fps)
    # Feed the live obs gauge with the same accounting the headline number
    # reports, so a snapshot taken during/after a bench run shows it.
    from tpu_render_cluster.obs import render_fps_gauge

    render_fps_gauge().set(median_fps)
    return median_fps


# Analytic per-ray-per-bounce FLOP counts for the fused path-trace
# kernel, which is OPAQUE to XLA's cost model (a tpu_custom_call).
# Counted from pallas_kernels._trace_kernel_factory's bounce_step: the
# branchless quadratic solve per sphere for the nearest hit, the same
# minus the argmin bookkeeping for the sun any-hit, and the per-lane
# shading tail (NEE + emission + sky + cosine resample + PCG RNG).
# Good to ~±50% — the point is order-of-magnitude roofline placement,
# not flop-exact attribution.
SPHERE_NEAREST_FLOPS_PER_SPHERE = 32
SPHERE_ANYHIT_FLOPS_PER_SPHERE = 26
SHADE_FLOPS_PER_RAY = 230


def chip_efficiency(fps: float, chunks: int, scene_name: str) -> dict:
    """Absolute efficiency accounting for the headline render.

    FLOPs and HBM bytes combine two sources: XLA's own cost model on the
    EXACT compiled program the fps was measured on
    (``compile().cost_analysis()`` — covers everything outside the render
    kernel), plus a documented analytic model of the fused Pallas kernel,
    which XLA reports as an opaque custom call. Scaled by the measured
    frame rate into achieved GFLOP/s, HBM GB/s, and a roofline position
    against the chip's published peaks.
    """
    import jax

    render_many = _make_render_many(chunks, scene_name)
    compiled = render_many.lower(1.0).compile()
    analysis = compiled.cost_analysis()
    flops_per_dispatch = float(analysis.get("flops", 0.0))
    bytes_per_dispatch = float(analysis.get("bytes accessed", 0.0))
    frames_per_dispatch = chunks * BATCH
    flops_per_frame = flops_per_dispatch / frames_per_dispatch
    bytes_per_frame = bytes_per_dispatch / frames_per_dispatch

    # In-kernel analytic part (the dominant term): every ray marches
    # MAX_BOUNCES fixed bounces against the padded sphere set.
    from tpu_render_cluster.render.scene import build_scene

    n_spheres = build_scene(scene_name, 1.0).centers.shape[0]
    rays = WIDTH * HEIGHT * SAMPLES
    per_ray_bounce = (
        n_spheres * (SPHERE_NEAREST_FLOPS_PER_SPHERE + SPHERE_ANYHIT_FLOPS_PER_SPHERE)
        + SHADE_FLOPS_PER_RAY
    )
    flops_per_frame += rays * BOUNCES * per_ray_bounce
    # Kernel HBM traffic: ray origins+directions in, radiance out (path
    # state itself stays VMEM-resident — that is the megakernel's point).
    bytes_per_frame += rays * (3 + 3 + 3) * 4

    from tpu_render_cluster.obs.profiling import chip_peaks

    kind = jax.devices()[0].device_kind
    peak_flops, peak_bw = chip_peaks(kind)

    achieved_flops = flops_per_frame * fps
    achieved_bw = bytes_per_frame * fps
    intensity = flops_per_frame / bytes_per_frame if bytes_per_frame else 0.0
    return {
        "flops_per_frame": round(flops_per_frame),
        "hbm_bytes_per_frame": round(bytes_per_frame),
        "gflops": round(achieved_flops / 1e9, 2),
        "hbm_gbps": round(achieved_bw / 1e9, 2),
        "arithmetic_intensity": round(intensity, 2),
        "device_kind": kind,
        "pct_of_peak": round(100.0 * achieved_flops / peak_flops, 3),
        "pct_of_peak_hbm_bw": round(100.0 * achieved_bw / peak_bw, 2),
        # Which roofline wall the kernel sits under at this intensity.
        "roofline_bound": (
            "compute" if intensity >= peak_flops / peak_bw else "memory"
        ),
    }


def _node_table_footprint(scene_name: str, cfg: dict) -> dict:
    """Bytes of the node tables a variant's kernels actually LOAD:
    fp32 nodes cost 36 B (6 f32 slabs + 3 int32 links), quant tier 1
    16 B (3 packed slab words + 1 meta word), tier 2 12 B. SAH builds
    ship octant-ordered tables — the SAME tree re-threaded 8x — so
    their resident table is 8x the canonical node count: the ordering
    trades table footprint for fewer node VISITS, while quant shrinks
    the bytes PER node; both are reported so neither win is conflated.
    """
    from tpu_render_cluster.render.mesh import (
        cached_mesh_bvh,
        cached_tlas_topology,
    )
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.scene import (
        build_mesh_instances,
        mesh_kind_for_scene,
    )

    kind = mesh_kind_for_scene(scene_name)
    if kind is None:
        return {}
    per_node = {0: 36, 1: 16, 2: 12}[cfg["quant"]]
    bvh = cached_mesh_bvh(kind, cfg["builder"], cfg["wide"])
    blas_nodes = int(bvh.skip.shape[0])
    orders = 8 if bvh.octant is not None else 1
    out = {
        "blas_nodes": blas_nodes,
        "octant_orders": orders,
        "bytes_per_node": per_node,
        "blas_bytes": blas_nodes * orders * per_node,
    }
    k = int(build_mesh_instances(scene_name, 1).translation.shape[0])
    if cfg["use_tlas"] and k > pk.tlas_leaf_size():
        tlas_nodes = int(
            cached_tlas_topology(k, pk.tlas_leaf_size()).skip.shape[0]
        )
        out["tlas_nodes"] = tlas_nodes
        out["total_bytes"] = (
            out["blas_bytes"] + tlas_nodes * orders * per_node
        )
    else:
        out["total_bytes"] = out["blas_bytes"]
    return out


def bvh_compare(
    deep_scene: str = "03_physics-2-mesh",
    control_scene: str = "02_physics-mesh",
    frames: int = 3,
    reps: int = 5,
    bounces: int = BOUNCES,
) -> dict:
    """BVH node-format/build A/B (ISSUE 10 hierarchy axis + ISSUE 15
    quant/SAH axis) through the masked fused renderer.

    Interleaved median-of-reps: each rep times every variant's window
    back to back on the SAME frame range, and the median cancels
    machine-load drift (per the recorded bench-variance protocol:
    sequential timings are invalid at this host's ±30%). Variants (see
    ``BVH_VARIANTS``): flat sweep, PR-10 TLAS baseline, binned-SAH +
    4-wide BLAS, 16-bit quantized nodes (+ packed carried state), and
    the combined quant+SAH headline. Two scenes:

    - ``deep_scene`` (03-family: deep BLAS x 48 instances) — the
      deep-scene cliff where the BLAS walk dominates;
    - ``control_scene`` (shallow megakernel mesh scene) — the
      no-regression guard.

    Each scene's section records per-variant roofline placement from the
    PR-9 ``cost_analysis`` capture — every variant lands under its own
    (tlas, quant, bvh) kernel-key dims — plus a computed BYTES-PER-RAY
    estimate (cost-model bytes accessed / rays per frame): the record
    shows the bytes the node formats remove, not just the frames/s
    delta. The masked tier's tonemapped frames are asserted
    uint8-identical across every variant (conservative quantized cull +
    order-invariant per-lane results), stamped ``images_identical``.

    On non-TPU hosts the masked tier is pinned to the Pallas interpret
    path for the duration (all variants must run the same kernel suite
    or the comparison is fiction). The committed record lives at
    results/BVH_BENCH.json; run with ``python bench.py --bvh-compare``
    on the target device class.
    """
    import statistics

    import jax
    import numpy as np

    from tpu_render_cluster.obs.profiling import (
        bvh_dims,
        get_profiler,
        kernel_key,
    )
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    on_tpu = jax.default_backend() == "tpu"
    pallas_pinned = False
    if not on_tpu and os.environ.get("TRC_PALLAS") is None:
        os.environ["TRC_PALLAS"] = "1"
        pallas_pinned = True
        jax.clear_caches()
        fused_frame_renderer.cache_clear()
    try:
        # CPU shrink: the workload must span many kernel blocks or the
        # measurement is dispatch overhead, but interpret mode caps what
        # is affordable.
        width = height = WIDTH if on_tpu else 128
        samples = SAMPLES if on_tpu else 1
        rays_per_frame = width * height * samples
        record: dict = {
            "metric": (
                f"BVH node-format variants (flat / TLAS / SAH+wide / "
                f"quantized) ({width}x{height}, {samples}spp, {bounces}b, "
                f"{jax.devices()[0].platform})"
            ),
            "unit": "frames/s/chip",
            "frames": frames,
            "reps": reps,
            "tlas_leaf": pk.tlas_leaf_size(),
            "variants": {
                name: dict(cfg) for name, cfg in BVH_VARIANTS.items()
            },
            "method_note": (
                "CPU-interpret proxy: the quant tiers' node/state byte "
                "compression (node_tables rows; 36 -> 16 B/node, carried "
                "pool tuple 13 -> 11 words) costs unpack ALU here and "
                "pays only on HBM-bandwidth-bound hardware — the "
                "frames/s axis on this host measures the SAH/wide/"
                "ordered-traversal half (fewer node visits) plus a small "
                "quant ALU tax; re-record on chip for the byte half. "
                "images_identical pins the masked tier bit-exact across "
                "every variant."
            ),
            "scenes": {},
        }
        profiler = get_profiler()
        for scene_name in (deep_scene, control_scene):
            renderers = {
                name: fused_frame_renderer(
                    scene_name, width, height, samples, bounces,
                    cfg["use_tlas"], cfg["quant"], cfg["builder"],
                    cfg["wide"],
                )
                for name, cfg in BVH_VARIANTS.items()
            }
            # Compile + warm, and pin the uint8 acceptance contract:
            # every node format renders the IDENTICAL tonemapped frame
            # (conservative quantized cull; per-lane results are
            # visit-order invariant).
            warm = {
                name: np.asarray(renderer(1))
                for name, renderer in renderers.items()
            }
            reference = warm["tlas"]
            images_identical = all(
                np.array_equal(img, reference) for img in warm.values()
            )
            fps: dict[str, list[float]] = {name: [] for name in renderers}
            for rep in range(reps):
                # Every variant renders the SAME frame window per rep
                # (physics-animated scenes: disjoint ranges would
                # compare different geometry).
                rep_frames = range(2 + rep * frames, 2 + (rep + 1) * frames)
                for name, renderer in renderers.items():
                    window = 0.0
                    for frame in rep_frames:
                        t0 = time.perf_counter()
                        np.asarray(renderer(frame))
                        elapsed = time.perf_counter() - t0
                        window += elapsed
                        # Measured-time pairing for the roofline rows
                        # (production gets this from the worker backend;
                        # the bench stands in for it here).
                        profiler.record_execute(renderer.kernel_key, elapsed)
                    fps[name].append(frames / window)
            section: dict = {"images_identical": bool(images_identical)}
            for name, values in fps.items():
                section[f"{name}_fps"] = round(statistics.median(values), 3)
            section["tlas_speedup"] = round(
                section["tlas_fps"] / section["flat_fps"], 3
            )
            # The ISSUE-15 acceptance ratio: quant+SAH combined vs the
            # PR-10 node format, same TLAS hierarchy on both sides.
            section["quant_sah_speedup"] = round(
                section["tlas_quant_sah_fps"] / section["tlas_fps"], 3
            )
            section["sah_speedup"] = round(
                section["tlas_sah_fps"] / section["tlas_fps"], 3
            )
            # Roofline placement per variant: each masked-tier kernel
            # key carries its own (tlas, quant, bvh) dims.
            roofline = profiler.view()
            kernels = roofline.get("kernels", {})
            placement: dict = {}
            for name, cfg in BVH_VARIANTS.items():
                entry = kernels.get(
                    kernel_key(
                        "masked", scene_name,
                        w=width, h=height, s=samples, b=bounces,
                        **bvh_dims(
                            tlas=cfg["use_tlas"], quant=cfg["quant"],
                            builder=cfg["builder"], wide=cfg["wide"],
                        ),
                    )
                )
                if entry and entry.get("captured"):
                    placement[name] = {
                        "flops": entry["flops"],
                        "bytes_accessed": entry["bytes_accessed"],
                        # The bytes/ray estimate the node formats attack:
                        # cost-model bytes accessed per compiled frame
                        # divided by the frame's primary rays.
                        "bytes_per_ray": round(
                            entry["bytes_accessed"] / rays_per_frame, 1
                        ),
                        "bound": entry.get("bound"),
                        "achieved_fraction_of_attainable": round(
                            entry.get(
                                "achieved_fraction_of_attainable", 0.0
                            ),
                            6,
                        ),
                    }
            if {"tlas", "tlas_quant_sah"} <= placement.keys():
                base_p = placement["tlas"]
                new_p = placement["tlas_quant_sah"]
                placement["delta"] = {
                    "flops_ratio": round(
                        new_p["flops"] / base_p["flops"], 4
                    ) if base_p["flops"] else None,
                    "bytes_ratio": round(
                        new_p["bytes_accessed"] / base_p["bytes_accessed"],
                        4,
                    ) if base_p["bytes_accessed"] else None,
                    "attainable_fraction_delta": round(
                        new_p["achieved_fraction_of_attainable"]
                        - base_p["achieved_fraction_of_attainable"],
                        6,
                    ),
                }
            section["roofline"] = placement
            # Analytic node-table footprint per variant: the bytes the
            # quant/SAH/wide formats actually remove. XLA cost analysis
            # cannot price a data-dependent walk (while-loop bodies are
            # counted once), so the whole-program bytes_per_ray above
            # barely moves — this row makes the table compression
            # visible: nodes x (36 B fp32 | 16 B 16-bit | 12 B 8-bit).
            section["node_tables"] = {
                name: _node_table_footprint(scene_name, cfg)
                for name, cfg in BVH_VARIANTS.items()
            }
            section["role"] = (
                "deep" if scene_name == deep_scene else "shallow-control"
            )
            record["scenes"][scene_name] = section
        return record
    finally:
        if pallas_pinned:
            os.environ.pop("TRC_PALLAS", None)
            jax.clear_caches()
            fused_frame_renderer.cache_clear()


def multi_job_bench(
    jobs: int = 3,
    frames: int = 8,
    workers: int = 4,
    reps: int = 5,
    render_seconds: float = 0.05,
) -> dict:
    """Serial admission vs concurrent fair-share on the sched/ service.

    Runs the SAME workload — ``jobs`` mock-render jobs of ``frames``
    frames each over ``workers`` in-process workers — through the
    multi-job scheduler twice per rep: once with
    ``TRC_SCHED_MAX_ACTIVE_JOBS=1`` (jobs admitted strictly one at a
    time, the single-job world's best case with zero restart overhead)
    and once with all jobs concurrent under weighted fair-share. The
    measured quantity is the service makespan (first admission to last
    job completion). Jobs are deliberately tail-heavy (few frames per
    worker), which is where concurrency pays: one job's wind-down tail
    leaves workers idle that the next job's frames can fill.

    ``reps`` interleaved repetitions, median per mode (the
    bench-variance protocol: this host measures ±30% run-to-run, so only
    interleaved median-of-reps A/B timings are meaningful). Mock-render
    measurement — this benchmarks the SCHEDULER, not the render plane.
    """
    import statistics

    from tpu_render_cluster.harness.local import run_local_multi_job
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.sched.models import JobSpec
    from tpu_render_cluster.worker.backends.mock import MockBackend

    def make_spec(index: int) -> JobSpec:
        job = BlenderJob(
            job_name=f"bench-mj-{index}",
            job_description="multi-job scheduler bench",
            project_file_path="%BASE%/p.blend",
            render_script_path="%BASE%/s.py",
            frame_range_from=1,
            frame_range_to=frames,
            wait_for_number_of_workers=workers,
            frame_distribution_strategy=DistributionStrategy.naive_fine(),
            output_directory_path="%BASE%/out",
            output_file_name_format="rendered-#####",
            output_file_format="PNG",
        )
        return JobSpec(job=job, weight=1.0)

    def run_once(max_active: int) -> float:
        saved = os.environ.get("TRC_SCHED_MAX_ACTIVE_JOBS")
        os.environ["TRC_SCHED_MAX_ACTIVE_JOBS"] = str(max_active)
        try:
            specs = [make_spec(i) for i in range(jobs)]
            backends = [
                MockBackend(render_seconds=render_seconds) for _ in range(workers)
            ]
            _traces, job_ids, manager, _workers = run_local_multi_job(
                specs, backends, timeout=300.0
            )
        finally:
            if saved is None:
                os.environ.pop("TRC_SCHED_MAX_ACTIVE_JOBS", None)
            else:
                os.environ["TRC_SCHED_MAX_ACTIVE_JOBS"] = saved
        runs = [manager._runs[job_id] for job_id in job_ids]
        first_admit = min(r.admitted_at for r in runs)
        last_finish = max(r.finished_at for r in runs)
        return last_finish - first_admit

    makespans: dict[str, list[float]] = {"serial": [], "concurrent": []}
    for _rep in range(reps):
        # Interleaved A/B: machine-load drift cancels across modes.
        makespans["serial"].append(run_once(1))
        makespans["concurrent"].append(run_once(jobs))
    record = {
        "metric": (
            f"sched multi-job makespan: {jobs} jobs x {frames} frames, "
            f"{workers} workers, mock render {render_seconds}s"
        ),
        "unit": "seconds (median of interleaved reps)",
        "jobs": jobs,
        "frames_per_job": frames,
        "workers": workers,
        "reps": reps,
        "serial_makespan_s": round(statistics.median(makespans["serial"]), 4),
        "concurrent_makespan_s": round(
            statistics.median(makespans["concurrent"]), 4
        ),
    }
    record["concurrent_speedup"] = round(
        record["serial_makespan_s"] / record["concurrent_makespan_s"], 3
    )
    return record


def _sched_env(overrides: dict) -> dict:
    """Apply env overrides, returning the saved values for restore."""
    saved = {}
    for key, value in overrides.items():
        saved[key] = os.environ.get(key)
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = str(value)
    return saved


def sched_bench(
    jobs: int = 64,
    frames: int = 600,
    workers: int = 2,
    reps: int = 3,
    queue_size: int = 4,
    tick_seconds: float = 0.002,
    scale_jobs: int = 16,
    window_seconds: float = 3.0,
    warmup_seconds: float = 0.5,
) -> dict:
    """Control-plane hot path A/B: incremental heap WFQ + preserialized
    dispatch frames vs the legacy full-rescan tick + per-send JSON.

    The SAME workload — ``jobs`` concurrent mock-render jobs, each with
    a ``frames``-frame backlog deep enough that NO job finishes inside
    the measurement window, over ``workers`` in-process workers with
    instant renders — runs once per rep under each stack:
    ``TRC_SCHED_TICK=scan + TRC_DISPATCH_FRAMES=encode`` (the pre-PR-17
    baseline) and ``TRC_SCHED_TICK=heap + TRC_DISPATCH_FRAMES=cached``.
    A driver waits until all ``jobs`` jobs are running, warms up for
    ``warmup_seconds``, then measures **assignments per second** over a
    fixed ``window_seconds`` window: queue-add messages actually sent
    (the ``transport_serialize_seconds{tag,direction=send}`` count
    delta), after which every job is cancelled and the service drains.
    The fixed window is the point: at steady state the legacy tick pays
    Θ(jobs × frames) per 2 ms cadence to re-derive what changed, so the
    dispatch rate collapses as the concurrent backlog grows, while the
    heap tick's O(dirty · log jobs) resync holds the line. Interleaved
    reps, median per mode (the bench-variance protocol).

    Also recorded: the ``share_scan`` tick-phase p99 per mode and, for
    the heap stack, at ``scale_jobs`` vs ``jobs`` concurrent jobs — the
    incremental tick's resync must grow SUBLINEARLY in job count where
    the legacy scan is Θ(jobs × frames). Every run additionally asserts
    exact both-ends wire accounting: the master's send bytes for
    ``request_frame-queue_add`` must equal the workers' summed recv
    bytes (the preserialized splice adds zero bytes and books the true
    wire text).
    """
    import statistics

    from tpu_render_cluster.harness.local import run_local_multi_job
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.obs.history import quantile_from_bucket_counts
    from tpu_render_cluster.sched.models import JobSpec
    from tpu_render_cluster.sched.tickprof import TICK_METRIC
    from tpu_render_cluster.transport.wirecost import (
        BYTES_METRIC,
        SERIALIZE_METRIC,
    )
    from tpu_render_cluster.worker.backends.mock import MockBackend

    TAG = "request_frame-queue_add"

    def make_spec(index: int) -> JobSpec:
        job = BlenderJob(
            job_name=f"bench-sched-{index:03d}",
            job_description="control-plane hot-path bench",
            project_file_path="%BASE%/p.blend",
            render_script_path="%BASE%/s.py",
            frame_range_from=1,
            frame_range_to=frames,
            wait_for_number_of_workers=workers,
            frame_distribution_strategy=DistributionStrategy.naive_fine(),
            output_directory_path="%BASE%/out",
            output_file_name_format="rendered-#####",
            output_file_format="PNG",
        )
        return JobSpec(job=job, weight=1.0 + (index % 3))

    def tag_series_total(snapshot: dict, name: str, direction: str) -> float:
        total = 0.0
        for key, value in snapshot.get(name, {}).get("series", {}).items():
            if f"tag={TAG}" in key and f"direction={direction}" in key:
                total += value["count"] if isinstance(value, dict) else value
        return total

    def run_once(mode: str, job_count: int) -> dict:
        window: dict = {}

        async def burst_driver(manager, _workers) -> None:
            job_ids = list(manager._runs.keys())
            while (
                sum(
                    1
                    for job_id in job_ids
                    if manager.job_status(job_id)["status"] == "running"
                )
                < job_count
            ):
                await asyncio.sleep(0.01)
            await asyncio.sleep(warmup_seconds)
            sends_0 = tag_series_total(
                manager.metrics.snapshot(), SERIALIZE_METRIC, "send"
            )
            t0 = time.perf_counter()
            await asyncio.sleep(window_seconds)
            sends_1 = tag_series_total(
                manager.metrics.snapshot(), SERIALIZE_METRIC, "send"
            )
            window["assignments"] = sends_1 - sends_0
            window["seconds"] = time.perf_counter() - t0
            for job_id in job_ids:
                await manager.cancel_job(job_id)

        saved = _sched_env(
            {
                "TRC_SCHED_TICK": mode,
                "TRC_DISPATCH_FRAMES": "cached" if mode == "heap" else "encode",
                "TRC_SCHED_MAX_ACTIVE_JOBS": job_count,
                "TRC_SCHED_TICK_SECONDS": tick_seconds,
                "TRC_SCHED_TARGET_QUEUE_SIZE": queue_size,
            }
        )
        try:
            specs = [make_spec(i) for i in range(job_count)]
            backends = [MockBackend(render_seconds=0.0) for _ in range(workers)]
            _traces, _job_ids, manager, worker_list = run_local_multi_job(
                specs, backends, timeout=600.0, driver=burst_driver
            )
        finally:
            _sched_env(saved)
        snapshot = manager.metrics.snapshot()
        assignments = window["assignments"]
        sent_bytes = tag_series_total(snapshot, BYTES_METRIC, "send")
        recv_bytes = sum(
            tag_series_total(w.metrics.snapshot(), BYTES_METRIC, "recv")
            for w in worker_list
        )
        # Exact both-ends agreement: the splice path books the true wire
        # text, never a re-encode — a single byte of drift fails the run.
        assert sent_bytes == recv_bytes, (
            f"wirecost disagreement ({mode}): master sent {sent_bytes} "
            f"bytes, workers received {recv_bytes}"
        )
        hist = manager.metrics.histogram(TICK_METRIC, labels=("phase",))
        series = hist.series(phase="share_scan")
        p99 = (
            quantile_from_bucket_counts(
                list(hist.buckets),
                list(series.counts) + [series.overflow],
                0.99,
            )
            if series is not None
            else None
        )
        return {
            "window_s": window["seconds"],
            "assignments": assignments,
            "assignments_per_s": assignments / window["seconds"],
            "share_scan_p99_s": p99,
            "wire_send_bytes": sent_bytes,
            "wire_recv_bytes": recv_bytes,
        }

    per_mode: dict[str, list[dict]] = {"scan": [], "heap": []}
    for _rep in range(reps):
        # Interleaved A/B: machine-load drift cancels across modes.
        per_mode["scan"].append(run_once("scan", jobs))
        per_mode["heap"].append(run_once("heap", jobs))
    # One heap run at the smaller job count for the sublinearity check
    # (same stack, only the concurrency changes).
    scale_run = run_once("heap", scale_jobs)

    def median_of(mode: str, field: str) -> float:
        return statistics.median(r[field] for r in per_mode[mode])

    record = {
        "metric": (
            f"sched control-plane A/B: {jobs} concurrent jobs x {frames}-"
            f"frame backlogs, {workers} workers, instant mock render, "
            f"tick {tick_seconds}s, queue {queue_size}, "
            f"{window_seconds}s steady-state window"
        ),
        "unit": "assignments/s (median of interleaved reps)",
        "jobs": jobs,
        "frames_per_job": frames,
        "workers": workers,
        "reps": reps,
        "tick_seconds": tick_seconds,
        "target_queue_size": queue_size,
        "window_seconds": window_seconds,
        "scan": {
            "tick_mode": "scan + per-send encode",
            "assignments_per_s": round(median_of("scan", "assignments_per_s"), 1),
            "share_scan_p99_s": median_of("scan", "share_scan_p99_s"),
        },
        "heap": {
            "tick_mode": "heap + preserialized frames",
            "assignments_per_s": round(median_of("heap", "assignments_per_s"), 1),
            "share_scan_p99_s": median_of("heap", "share_scan_p99_s"),
        },
        "wirecost_exact_agreement": True,  # asserted per run above
    }
    record["speedup_assignments_per_s"] = round(
        record["heap"]["assignments_per_s"]
        / max(1e-9, record["scan"]["assignments_per_s"]),
        3,
    )
    # Sublinearity: heap share_scan p99 at `jobs` vs `scale_jobs`
    # concurrent jobs must grow slower than the job-count ratio.
    p99_small = scale_run["share_scan_p99_s"]
    p99_large = record["heap"]["share_scan_p99_s"]
    record["share_scan_scaling"] = {
        "jobs_small": scale_jobs,
        "p99_small_s": p99_small,
        "jobs_large": jobs,
        "p99_large_s": p99_large,
        "p99_growth": (
            round(p99_large / p99_small, 3) if p99_small else None
        ),
        "job_count_ratio": round(jobs / scale_jobs, 3),
    }
    return record


def _ha_shard_process(
    conn, worker_count: int, render_seconds: float, replicate: bool = False
) -> None:
    """One master SHARD as its own OS process (multiprocessing spawn
    target; must stay module-level picklable).

    Runs a LEDGER-BACKED ``sched.JobManager`` + its JSON-lines control
    server + its slice of the worker pool colocated in one asyncio loop
    — exactly the HA deployment shape (a shard you cannot fail over is
    not a control plane, so the write-ahead ledger's fsync-per-result
    durability cost is part of what is measured) — reports the control
    port back over the pipe, serves until the router's drain lands, then
    reports how many units finished and the admission->completion wall
    window.

    With ``replicate`` the shard also streams its ledger to one attached
    ``LedgerFollower`` over TCP (ha/replicate.py, a DISJOINT replica
    directory — the cross-host deployment shape, colocated only for the
    bench), and reports the follower's apply-lag sample distribution so
    the A/B prices what the durability upgrade costs the hot path.
    """
    import asyncio
    import tempfile

    from tpu_render_cluster.ha.ledger import JobLedger
    from tpu_render_cluster.obs import MetricsRegistry
    from tpu_render_cluster.sched.control import ControlServer
    from tpu_render_cluster.sched.manager import JobManager
    from tpu_render_cluster.worker.backends.mock import MockBackend
    from tpu_render_cluster.worker.runtime import Worker

    async def serve() -> dict:
        registry = MetricsRegistry()
        # The shard's registry also receives the ledger's append-latency
        # histogram (ha_ledger_append_seconds): the fsync-per-transition
        # cost is part of what the shard A/B measures, so report it.
        ledger = JobLedger.open(
            tempfile.mkdtemp(prefix="trc-ha-bench-"), metrics=registry
        )
        manager = JobManager(
            "127.0.0.1", 0, metrics=registry, ledger=ledger
        )
        replication = None
        follower = None
        if replicate:
            from tpu_render_cluster.ha.replicate import (
                LedgerFollower,
                ReplicationServer,
            )

            replication = ReplicationServer(ledger, metrics=registry)
            await replication.start()
            follower = LedgerFollower(
                tempfile.mkdtemp(prefix="trc-ha-bench-replica-"),
                "127.0.0.1",
                replication.port,
                metrics=MetricsRegistry(),
                follower_id="bench-follower",
            )
            follower.start()
        serve_task = asyncio.create_task(manager.serve())
        while manager._server is None:
            if serve_task.done():
                await serve_task
                raise RuntimeError("shard manager exited before startup")
            await asyncio.sleep(0.01)
        control = ControlServer(manager, "127.0.0.1", 0)
        await control.start()
        workers = [
            Worker(
                "127.0.0.1",
                manager.port,
                MockBackend(render_seconds=render_seconds),
                metrics=MetricsRegistry(),
            )
            for _ in range(worker_count)
        ]
        worker_tasks = [
            asyncio.create_task(w.connect_and_run_to_job_completion())
            for w in workers
        ]
        conn.send({"control_port": control.port})
        await serve_task
        await control.stop()
        _done, pending = await asyncio.wait(worker_tasks, timeout=5.0)
        for task in pending:
            task.cancel()
        await asyncio.gather(*worker_tasks, return_exceptions=True)
        runs = [r for r in manager._runs.values() if r.state is not None]
        out = {
            "units": sum(r.state.finished_count() for r in runs),
            "first_admit": min(
                (r.admitted_at for r in runs if r.admitted_at), default=0.0
            ),
            "last_finish": max(
                (r.finished_at for r in runs if r.finished_at), default=0.0
            ),
        }
        # The ledger's per-append durability cost (ha_ledger_append_seconds,
        # fsync included) rides back raw so the parent can fold one
        # cross-shard distribution and report its percentiles.
        histogram = manager.metrics.histogram("ha_ledger_append_seconds")
        series = histogram.series()
        if series is not None:
            out["append_bounds"] = list(histogram.buckets)
            out["append_buckets"] = list(series.counts) + [series.overflow]
            out["append_count"] = series.count
            out["append_sum"] = series.sum
        # Raw registry snapshots (shard + every colocated worker) ride
        # back so the parent can fold one whole-stack attribution report
        # across the rep — tick phases, loop lag, and wire costs all live
        # in these per-process registries, not the parent's.
        out["registry"] = manager.metrics.snapshot()
        out["worker_registries"] = [w.metrics.snapshot() for w in workers]
        if follower is not None:
            # Let the tail drain before the lag readout: the stream is
            # asynchronous by design, so the final few records may still
            # be in flight when the last unit finishes.
            head = ledger.replay.last_seq
            deadline = asyncio.get_running_loop().time() + 10.0
            while (
                follower.last_seq < head
                and asyncio.get_running_loop().time() < deadline
            ):
                await asyncio.sleep(0.02)
            from tpu_render_cluster.chaos.runner import unit_latency_stats

            out["replication"] = {
                "records_applied": follower.records_applied,
                "behind_units": max(0, head - follower.last_seq),
                "lag": unit_latency_stats(list(follower.lag_samples)),
            }
            await follower.stop()
            await replication.stop()
        return out

    try:
        conn.send(asyncio.run(serve()))
    except Exception as e:  # noqa: BLE001 - report instead of a silent hang
        conn.send({"error": f"{type(e).__name__}: {e}"})
    finally:
        conn.close()


def _balanced_job_names(count: int, shards: int) -> list[str]:
    """``count`` job names whose crc32 hash splits EVENLY across
    ``shards`` (found by scanning candidates through the real router
    hash): the 2-shard makespan then measures throughput, not the luck
    of an uneven split."""
    from tpu_render_cluster.ha.shards import shard_for_job_name

    quota = count // shards
    per = dict.fromkeys(range(shards), 0)
    names: list[str] = []
    candidate = 0
    while len(names) < count:
        name = f"ha-bench-{candidate:04d}"
        candidate += 1
        shard = shard_for_job_name(name, shards)
        if per[shard] < quota or all(v >= quota for v in per.values()):
            per[shard] += 1
            names.append(name)
    return names


def ha_shard_bench(
    total_workers: int = 32,
    jobs: int = 12,
    frames: int = 100,
    reps: int = 5,
    render_seconds: float = 0.0005,
    failover_reps: int = 3,
    failover_seed: int = 99,
) -> dict:
    """Aggregate assignments/s at 1 vs 2 control-plane shards + MTTR.

    The A/B holds the WORKLOAD and the worker count constant — ``jobs``
    mock jobs of ``frames`` frames over ``total_workers`` workers — and
    varies only how many master processes serve it: one shard (the
    single-master deployment, everything on one event loop/GIL) vs two
    (each master process owns half the workers and the jobs the router
    hashes to it, with balanced names so the split is even). Renders are
    ~free (``render_seconds``) and the scheduler tick compressed, so the
    measured quantity is control-plane throughput: units finished per
    second of admission->completion wall time, summed across shards over
    the combined window. Interleaved median-of-reps per the
    bench-variance protocol.

    The failover half runs the seeded master-kill chaos scenario
    (ha/chaos.py) ``failover_reps`` times and reports the median MTTR
    (kill -> first post-adoption assignment) with every run's invariant
    audit required green.
    """
    import asyncio
    import multiprocessing
    import statistics

    from tpu_render_cluster.ha.shards import ShardRouter
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.obs import MetricsRegistry

    ctx = multiprocessing.get_context("spawn")
    sched_env = {
        # Compress the dispatch tick and deepen the per-worker queues so
        # the master process is CPU-saturated (measured cpu/wall ~= 1.0,
        # one full core of event-loop/RPC work) rather than tick-idle:
        # control-plane throughput is the quantity sharding must scale.
        "TRC_SCHED_TICK_SECONDS": "0.002",
        "TRC_SCHED_TARGET_QUEUE_SIZE": "8",
        "TRC_SCHED_MAX_ACTIVE_JOBS": str(jobs),
    }

    def make_job_dict(name: str, barrier: int) -> dict:
        return BlenderJob(
            job_name=name,
            job_description="ha shard bench",
            project_file_path="%BASE%/p.blend",
            render_script_path="%BASE%/s.py",
            frame_range_from=1,
            frame_range_to=frames,
            wait_for_number_of_workers=barrier,
            frame_distribution_strategy=DistributionStrategy.naive_fine(),
            output_directory_path="%BASE%/out",
            output_file_name_format="rendered-#####",
            output_file_format="PNG",
        ).to_dict()

    append_stats: dict[str, object] = {}
    attrib_snapshots: list[dict[str, object]] = []
    attrib_window = 0.0
    repl_sections: list[dict] = []

    def run_once(shard_count: int, replicate: bool = False) -> float:
        nonlocal append_stats, attrib_snapshots, attrib_window
        workers_per_shard = total_workers // shard_count
        saved = {k: os.environ.get(k) for k in sched_env}
        os.environ.update(sched_env)
        procs, pipes = [], []
        try:
            for _ in range(shard_count):
                parent_conn, child_conn = ctx.Pipe()
                proc = ctx.Process(
                    target=_ha_shard_process,
                    args=(
                        child_conn,
                        workers_per_shard,
                        render_seconds,
                        replicate,
                    ),
                )
                proc.start()
                child_conn.close()
                procs.append(proc)
                pipes.append(parent_conn)
            endpoints = []
            for pipe in pipes:
                startup = pipe.recv()
                if "control_port" not in startup:
                    raise RuntimeError(f"shard failed to start: {startup}")
                endpoints.append(("127.0.0.1", startup["control_port"]))
            router = ShardRouter(endpoints, metrics=MetricsRegistry())
            names = _balanced_job_names(jobs, shard_count)

            async def drive() -> None:
                for name in names:
                    response = await router.handle_request(
                        {
                            "op": "submit",
                            "spec": {
                                "job": make_job_dict(name, workers_per_shard)
                            },
                        }
                    )
                    if not response.get("ok"):
                        raise RuntimeError(f"submit failed: {response}")
                drained = await router.handle_request({"op": "drain"})
                if not drained.get("ok"):
                    raise RuntimeError(f"drain failed: {drained}")

            asyncio.run(drive())
            results = [pipe.recv() for pipe in pipes]
            for result in results:
                if "error" in result:
                    raise RuntimeError(f"shard failed: {result['error']}")
                if "replication" in result:
                    repl_sections.append(result["replication"])
            total_units = sum(r["units"] for r in results)
            # Fold every shard's ledger-append histogram into one
            # distribution (shared DEFAULT_BUCKETS bounds): the fsync
            # cost per journaled transition, now a headline number.
            from tpu_render_cluster.obs.history import (
                quantile_from_bucket_counts,
            )

            bounds = next(
                (r["append_bounds"] for r in results if "append_bounds" in r),
                None,
            )
            if bounds is not None:
                merged = [0.0] * (len(bounds) + 1)
                count, total_s = 0, 0.0
                for r in results:
                    if "append_buckets" not in r:
                        continue
                    for i, c in enumerate(r["append_buckets"][: len(merged)]):
                        merged[i] += c
                    count += r["append_count"]
                    total_s += r["append_sum"]
                if count:
                    append_stats = {
                        "appends": count,
                        "mean_s": total_s / count,
                        "p50_s": quantile_from_bucket_counts(bounds, merged, 0.5),
                        "p99_s": quantile_from_bucket_counts(bounds, merged, 0.99),
                    }
            window = max(r["last_finish"] for r in results) - min(
                r["first_admit"] for r in results
            )
            # Keep the LAST rep's registries (shards + colocated workers)
            # for the record's whole-stack attribution section.
            attrib_snapshots = [
                {"metrics": r["registry"]} for r in results if "registry" in r
            ] + [
                {"metrics": snap}
                for r in results
                for snap in r.get("worker_registries", ())
            ]
            attrib_window = window
            if total_units != jobs * frames:
                raise RuntimeError(
                    f"{shard_count}-shard run finished {total_units} units, "
                    f"expected {jobs * frames}"
                )
            return total_units / max(1e-9, window)
        finally:
            for proc in procs:
                proc.join(timeout=30.0)
                if proc.is_alive():
                    proc.terminate()
            for name, value in saved.items():
                if value is None:
                    os.environ.pop(name, None)
                else:
                    os.environ[name] = value

    rates: dict[str, list[float]] = {"1": [], "1r": [], "2": []}
    for _rep in range(reps):
        # Interleaved A/B: machine-load drift cancels across modes. The
        # "1r" leg is the replication A/B — one shard streaming its
        # ledger to an attached follower over TCP, same workload.
        rates["1"].append(run_once(1))
        rates["1r"].append(run_once(1, replicate=True))
        rates["2"].append(run_once(2))

    from tpu_render_cluster.chaos.plan import FaultPlan
    from tpu_render_cluster.ha.chaos import (
        run_chaos_failover_job,
        run_chaos_replicated_failover,
    )

    mttrs = []
    for rep in range(failover_reps):
        plan = FaultPlan.generate_failover(failover_seed + rep, 3)
        report = run_chaos_failover_job(plan, frames=48, timeout=180.0)
        if not report.ok:
            raise RuntimeError(
                f"failover rep {rep} violated invariants: {report.violations}"
            )
        mttr = report.stats["failover"].get("mttr_seconds")
        if mttr is not None:
            mttrs.append(mttr)

    # The 1-follower MTTR: the ledger reaches the standby by streaming
    # replication ONLY (no shared filesystem), and the promotion is the
    # router's — detection + promote + epoch-fenced adoption all priced.
    replicated_mttrs = []
    for rep in range(failover_reps):
        plan = FaultPlan.generate_replicated_failover(failover_seed + rep, 3)
        report = run_chaos_replicated_failover(plan, frames=48, timeout=180.0)
        if not report.ok:
            raise RuntimeError(
                f"replicated failover rep {rep} violated invariants: "
                f"{report.violations}"
            )
        mttr = report.stats["failover"].get("mttr_seconds")
        if mttr is not None:
            replicated_mttrs.append(mttr)

    lag_p50s = [
        s["lag"]["p50_s"] for s in repl_sections if s["lag"].get("count")
    ]
    lag_p99s = [
        s["lag"]["p99_s"] for s in repl_sections if s["lag"].get("count")
    ]
    record = {
        "metric": (
            f"control-plane shard scaling: {jobs} jobs x {frames} units over "
            f"{total_workers} workers, 1 vs 2 master shard processes "
            f"(router-hashed, balanced names), mock render "
            f"{render_seconds * 1000:.1f}ms"
        ),
        "unit": "assignments/s (units finished per second of combined "
        "admission->completion window; median of interleaved reps)",
        "method": (
            "each shard = one OS process running sched.JobManager + JSON-"
            "lines control + its slice of the worker pool; submissions "
            "routed by ha.shards.ShardRouter over real sockets; "
            "TRC_SCHED_TICK_SECONDS=0.002 + TRC_SCHED_TARGET_QUEUE_SIZE=8 "
            "keep the master process CPU-saturated (cpu/wall ~1.0) so the "
            "event loop's dispatch/RPC work, not tick idling or render "
            "time, is the measured bottleneck; interleaved "
            "median-of-reps per the bench-variance protocol. The "
            "replication A/B re-runs the 1-shard leg with a TCP-attached "
            "ledger follower (ha/replicate.py) and reports the apply-lag "
            "percentiles. MTTR from seeded ha/chaos master-kill runs "
            "(kill -> first standby dispatch), shared-directory standby "
            "vs streamed-replica router promotion, every run's invariant "
            "audit green."
        ),
        "total_workers": total_workers,
        "jobs": jobs,
        "frames_per_job": frames,
        "reps": reps,
        "assignments_per_s_1_shard": round(statistics.median(rates["1"]), 1),
        "assignments_per_s_2_shards": round(statistics.median(rates["2"]), 1),
        "all_reps_1_shard": [round(r, 1) for r in rates["1"]],
        "all_reps_2_shards": [round(r, 1) for r in rates["2"]],
        "failover": {
            "reps": failover_reps,
            "seed_base": failover_seed,
            "mttr_seconds_median": (
                round(statistics.median(mttrs), 3) if mttrs else None
            ),
            "mttr_seconds_all": [round(m, 3) for m in mttrs],
        },
        # The replication A/B: the same 1-shard workload with a follower
        # attached (streaming every committed record over TCP) vs none,
        # plus the MTTR when failover rides the stream instead of a
        # shared directory (seeded router-promotion chaos runs).
        "replication": {
            "assignments_per_s_no_follower": round(
                statistics.median(rates["1"]), 1
            ),
            "assignments_per_s_1_follower": round(
                statistics.median(rates["1r"]), 1
            ),
            "all_reps_1_follower": [round(r, 1) for r in rates["1r"]],
            "follower_overhead_pct": round(
                100.0
                * (
                    1.0
                    - statistics.median(rates["1r"])
                    / max(1e-9, statistics.median(rates["1"]))
                ),
                1,
            ),
            "lag_p50_s": (
                statistics.median(lag_p50s) if lag_p50s else None
            ),
            "lag_p99_s": (
                statistics.median(lag_p99s) if lag_p99s else None
            ),
            "behind_units_at_drain": (
                max(s["behind_units"] for s in repl_sections)
                if repl_sections
                else None
            ),
            "failover": {
                "reps": failover_reps,
                "seed_base": failover_seed,
                "mttr_seconds_median": (
                    round(statistics.median(replicated_mttrs), 3)
                    if replicated_mttrs
                    else None
                ),
                "mttr_seconds_all": [round(m, 3) for m in replicated_mttrs],
            },
        },
        # Per-append ledger durability cost (fsync incl.) folded across
        # the final rep's shards — the ha_ledger_append_seconds histogram
        # that PR 12's HA metrics satellite made visible.
        "ledger_append": append_stats or None,
    }
    record["shard_scaling"] = round(
        record["assignments_per_s_2_shards"]
        / max(1e-9, record["assignments_per_s_1_shard"]),
        3,
    )
    # Whole-stack attribution over the final (2-shard) rep's registries:
    # where the combined admission->completion window went — control
    # plane vs wire vs queue wait — with the window x worker-count pool
    # as the denominator. Accounting must never kill the bench.
    try:
        from tpu_render_cluster.analysis.obs_events import (
            summarize_attribution,
        )

        if attrib_snapshots and attrib_window > 0:
            attribution = summarize_attribution(
                attrib_snapshots,
                worker_seconds=attrib_window * total_workers,
            )
            if attribution:
                record["attribution"] = attribution
    except Exception as e:  # noqa: BLE001 - accounting must not kill the bench
        print(f"warning: attribution accounting failed: {e}", file=sys.stderr)
    return record


def speculation_bench(
    workers: int = 3,
    frames: int = 24,
    reps: int = 5,
    seed: int = 1205,
    straggler_multiplier: float = 6.0,
    render_seconds: float = 0.12,
) -> dict:
    """Speculation-on vs -off on a seeded tail-heavy straggler workload.

    The workload is the chaos harness's real cluster stack (dynamic
    work-stealing strategy, real localhost WebSockets, mock renders)
    under a deterministic seeded fault plan that makes ``workers - 1``
    of the workers ``straggler_multiplier``x slow — the recorded
    heterogeneous/tail-heavy shape where the makespan is gated by the
    last unit rendering on a straggler and stealing cannot help (a
    RENDERING unit cannot be unqueued). Speculation-on runs add
    ``TRC_SPECULATION=1``: the predicted/overdue tail unit is duplicated
    onto the fastest idle worker and the first result wins through the
    dedup ledger.

    Measured per run: the job makespan and the EXACT p99 of per-unit
    winning-result latencies (state.unit_seconds). ``reps`` interleaved
    off/on repetitions, median per mode (the bench-variance protocol:
    this host measures +-30% run-to-run, so only interleaved
    median-of-reps A/B timings are meaningful). EVERY run — both modes —
    must pass the full chaos invariant audit (exactly-once ledger, no
    ghost mirrors, valid merged trace); a violation fails the bench.
    """
    import statistics

    from tpu_render_cluster.chaos.plan import ChaosTimings, FaultEvent, FaultPlan
    from tpu_render_cluster.chaos.runner import run_chaos_job

    # Deterministic pure-data plan (fingerprinted in the record): every
    # slot but the last renders straggler_multiplier-x slow.
    plan = FaultPlan(
        seed=seed,
        workers=workers,
        events=tuple(
            FaultEvent(
                kind="slow_render",
                target=slot,
                multiplier=straggler_multiplier,
            )
            for slot in range(workers - 1)
        ),
        timings=ChaosTimings(),
    )

    spec_env = {
        "TRC_SPECULATION": None,  # set per run
        "TRC_SPEC_THRESHOLD": "1.5",
        "TRC_SPEC_MIN_SAMPLES": "2",
    }

    def run_once(spec_on: bool) -> tuple[float, float, dict | None]:
        saved = {name: os.environ.get(name) for name in spec_env}
        os.environ.update(
            {name: value for name, value in spec_env.items() if value}
        )
        os.environ["TRC_SPECULATION"] = "1" if spec_on else "0"
        try:
            report = run_chaos_job(
                plan, frames=frames, render_seconds=render_seconds, timeout=180.0
            )
        finally:
            for name, value in saved.items():
                if value is None:
                    os.environ.pop(name, None)
                else:
                    os.environ[name] = value
        if not report.ok:
            raise RuntimeError(
                f"chaos audit failed (speculation={'on' if spec_on else 'off'}): "
                f"{report.violations}"
            )
        return (
            float(report.stats["job_seconds"]),
            float(report.stats["unit_latency"].get("p99_s", 0.0)),
            report.stats.get("speculation"),
        )

    makespans: dict[str, list[float]] = {"off": [], "on": []}
    p99s: dict[str, list[float]] = {"off": [], "on": []}
    speculation_views: list[dict] = []
    for _rep in range(reps):
        # Interleaved A/B: machine-load drift cancels across modes.
        makespan, p99, _ = run_once(False)
        makespans["off"].append(makespan)
        p99s["off"].append(p99)
        makespan, p99, view = run_once(True)
        makespans["on"].append(makespan)
        p99s["on"].append(p99)
        if view is not None:
            speculation_views.append(view)
    launched = sum(v.get("launched", 0) for v in speculation_views)
    outcomes: dict[str, int] = {}
    for view in speculation_views:
        for outcome, count in (view.get("outcomes") or {}).items():
            outcomes[outcome] = outcomes.get(outcome, 0) + int(count)
    record = {
        "metric": (
            f"speculative tail-unit re-execution: {frames} frames, "
            f"{workers} workers ({workers - 1} stragglers "
            f"{straggler_multiplier}x slow), seeded chaos stack"
        ),
        "unit": "seconds (median of interleaved reps)",
        "workers": workers,
        "frames": frames,
        "reps": reps,
        "plan_fingerprint": plan.fingerprint(),
        "straggler_multiplier": straggler_multiplier,
        "render_seconds": render_seconds,
        "audits": "every run (both modes) passed the full chaos "
        "invariant audit incl. ok_results - duplicate_results == "
        "units_total",
        "makespan_off_s": round(statistics.median(makespans["off"]), 4),
        "makespan_on_s": round(statistics.median(makespans["on"]), 4),
        "unit_p99_off_s": round(statistics.median(p99s["off"]), 4),
        "unit_p99_on_s": round(statistics.median(p99s["on"]), 4),
        "speculations_launched": launched,
        "speculation_outcomes": outcomes,
    }
    record["makespan_speedup"] = round(
        record["makespan_off_s"] / record["makespan_on_s"], 3
    )
    record["unit_p99_speedup"] = round(
        record["unit_p99_off_s"] / record["unit_p99_on_s"], 3
    )
    return record


def tile_scaling_bench(
    workers_list: tuple[int, ...] = (1, 2, 4),
    reps: int = 5,
    base_render_seconds: float = 0.8,
) -> dict:
    """Single-frame latency vs worker count, whole-frame vs tile-sharded.

    The PR-7 claim is that tiles make per-frame LATENCY (not just
    throughput) scale with cluster size: a 1-frame job over N workers is
    floored at one worker's speed when the unit of distribution is the
    whole frame, and approaches T/tiles + overhead when it is a tile.

    Two sections, per the recorded bench-variance protocol (interleaved
    median-of-reps only; ±30% run-to-run on this host):

    - **latency matrix** (the headline): one 1-frame job per (workers x
      grid) config through the REAL cluster stack — dispatch RPCs, tile
      piggybacks, per-unit events, the assembly barrier — with a
      mock-render proxy whose per-unit duration models a fixed per-pixel
      cost (tile = base / tiles_per_frame). A CPU-core-bound host cannot
      honestly parallelize real XLA renders (this box has too few cores
      to separate scheduler scaling from core contention), so the proxy
      measures what the CLUSTER adds over the ideal split — re-record
      with the tpu-raytrace backend on a multi-chip pool for the
      hardware number.
    - **seam correctness**: a real 2-worker TILED cluster run with the
      tpu-raytrace backend (TRC_PALLAS interpret path) — workers write
      tile files, the master stitches — compared pixel-for-pixel against
      a 1-worker UNTILED run of the same frame.
    """
    import statistics

    from tpu_render_cluster.harness.local import _run_local_job_full
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.worker.backends.mock import MockBackend

    grids: tuple[tuple[int, int] | None, ...] = (None, (2, 2))

    def make_job(tag: str, workers: int, grid) -> BlenderJob:
        return BlenderJob(
            job_name=f"04vs-tile-bench-{tag}",
            job_description="tile scaling bench",
            project_file_path="%BASE%/p.blend",
            render_script_path="%BASE%/s.py",
            frame_range_from=1,
            frame_range_to=1,
            wait_for_number_of_workers=workers,
            frame_distribution_strategy=DistributionStrategy.naive_fine(),
            output_directory_path="%BASE%/out",
            output_file_name_format="rendered-#####",
            output_file_format="PNG",
            tile_grid=grid,
        )

    def run_once(workers: int, grid) -> float:
        tiles = 1 if grid is None else grid[0] * grid[1]
        job = make_job(f"{workers}w-{tiles}t", workers, grid)
        backends = [
            MockBackend(
                load_seconds=0.0,
                save_seconds=0.0,
                render_seconds=base_render_seconds / tiles,
            )
            for _ in range(workers)
        ]
        master_trace, _traces, _manager, _workers = _run_local_job_full(
            job, backends, 120.0
        )
        return master_trace.job_finish_time - master_trace.job_start_time

    latencies: dict[str, list[float]] = {}
    for rep in range(reps):
        # Interleaved across EVERY config per rep: machine-load drift
        # cancels across the whole matrix, not just within a pair.
        for workers in workers_list:
            for grid in grids:
                key = f"{workers}w_{'1x1' if grid is None else f'{grid[0]}x{grid[1]}'}"
                latencies.setdefault(key, []).append(run_once(workers, grid))

    record: dict = {
        "metric": (
            "single-frame latency vs workers, whole-frame vs tile-sharded "
            f"(mock render {base_render_seconds}s/frame, tile = frame/tiles)"
        ),
        "unit": "seconds (median of interleaved reps)",
        "method": (
            "real cluster stack (dispatch RPCs, tile piggyback, assembly "
            "barrier) with a mock per-pixel-cost render proxy — CPU proxy "
            "per ISSUE 7 (this host cannot parallelize real XLA renders "
            f"across {os.cpu_count()} cores); re-record on a multi-chip "
            "pool with tpu-raytrace backends"
        ),
        "reps": reps,
        "base_render_seconds": base_render_seconds,
        "latency_s": {
            key: round(statistics.median(values), 4)
            for key, values in latencies.items()
        },
    }
    # Headline ratios: tiled latency speedup over the whole-frame floor
    # at the same worker count.
    for workers in workers_list:
        whole = statistics.median(latencies[f"{workers}w_1x1"])
        tiled = statistics.median(latencies[f"{workers}w_2x2"])
        record[f"tiled_speedup_{workers}w"] = round(whole / tiled, 3)

    record["seam_check"] = _tile_seam_check()
    return record


def _tile_seam_check() -> dict:
    """Whole-frame vs master-assembled tiled render of the SAME frame,
    through real clusters (tpu-raytrace backends, Pallas interpret path,
    tiny image): the stitched output file must be pixel-identical."""
    import tempfile

    import numpy as np
    from PIL import Image

    from tpu_render_cluster.harness.local import run_local_job
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    saved = os.environ.get("TRC_PALLAS")
    os.environ["TRC_PALLAS"] = "1"
    try:
        import jax

        jax.clear_caches()
        results: dict[str, str] = {}
        with tempfile.TemporaryDirectory() as tmp:
            for label, grid, workers in (("whole", None, 1), ("tiled", (2, 2), 2)):
                out = os.path.join(tmp, label)
                job = BlenderJob(
                    job_name=f"04_very-simple_seam-{label}",
                    job_description="tile seam check",
                    project_file_path="%BASE%/p.blend",
                    render_script_path="%BASE%/s.py",
                    frame_range_from=1,
                    frame_range_to=1,
                    wait_for_number_of_workers=workers,
                    frame_distribution_strategy=DistributionStrategy.naive_fine(),
                    output_directory_path=out,
                    output_file_name_format="rendered-#####",
                    output_file_format="PNG",
                    tile_grid=grid,
                )
                backends = [
                    TpuRaytraceBackend(
                        width=16, height=16, samples=2, max_bounces=3
                    )
                    for _ in range(workers)
                ]
                run_local_job(job, backends, timeout=600.0)
                results[label] = os.path.join(out, "rendered-00001.png")
            whole = np.asarray(Image.open(results["whole"]).convert("RGB"))
            tiled = np.asarray(Image.open(results["tiled"]).convert("RGB"))
            diff = np.abs(whole.astype(int) - tiled.astype(int))
            return {
                "scene": "04_very-simple (16x16, 2spp, 3 bounces, "
                "Pallas interpret)",
                "pixels": int(whole.shape[0] * whole.shape[1]),
                "max_abs_diff_u8": int(diff.max()),
                "mae_u8": round(float(diff.mean()), 6),
                "identical": bool((diff == 0).all()),
            }
    finally:
        if saved is None:
            os.environ.pop("TRC_PALLAS", None)
        else:
            os.environ["TRC_PALLAS"] = saved
        import jax

        jax.clear_caches()


def cpu_baseline_fps() -> float:
    pinned = os.environ.get("BENCH_CPU_FPS")
    if pinned:
        return float(pinned)
    # This parent has touched JAX and holds the chip; the child is pinned
    # to the CPU, so it never asks for it.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # The CPU baseline uses the pure-XLA path: Pallas interpret mode is a
    # debugging path and would understate the baseline.
    env["TRC_PALLAS"] = "0"
    env.pop("BENCH_CPU_FPS", None)
    result = subprocess.run(
        [sys.executable, __file__, "--cpu-probe"],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in result.stdout.splitlines():
        if line.startswith("CPU_FPS="):
            return float(line.split("=", 1)[1])
    raise RuntimeError(
        f"CPU probe failed (rc={result.returncode}): {result.stderr[-400:]}"
    )


def _int_flag(name: str, default: int) -> int:
    """Value of ``<name> <int>`` in argv, or ``default`` when absent
    (also when the flag is the trailing token with its value omitted)."""
    if name in sys.argv:
        index = sys.argv.index(name) + 1
        if index < len(sys.argv):
            return int(sys.argv[index])
    return default


def _str_flag(name: str, default: str) -> str:
    """Value of ``<name> <str>`` in argv, or ``default`` when absent
    (also when the flag is the trailing token with its value omitted)."""
    if name in sys.argv:
        index = sys.argv.index(name) + 1
        if index < len(sys.argv):
            return sys.argv[index]
    return default


def main() -> int:
    from tpu_render_cluster.utils.accelerator import configure_compile_cache

    configure_compile_cache()
    if "--cpu-probe" in sys.argv:
        # Smaller sample for the slow CPU path (~1 fps): one 8-frame
        # dispatch, one window; fps scales linearly in frames.
        print(f"CPU_FPS={measure_fps(reps=1, min_window_s=0.0, chunks=1)}")
        return 0

    if "--multi-job" in sys.argv:

        jobs = _int_flag("--jobs", 3)
        frames = _int_flag("--frames", 8)
        workers = _int_flag("--workers", 4)
        reps = _int_flag("--reps", 5)
        record = multi_job_bench(jobs=jobs, frames=frames, workers=workers, reps=reps)
        record["command"] = (
            f"python bench.py --multi-job --jobs {jobs} --frames {frames} "
            f"--workers {workers} --reps {reps}"
        )
        print(json.dumps(record))
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "results",
            "MULTIJOB_BENCH.json",
        )
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        return 0

    if "--sched" in sys.argv:
        jobs = _int_flag("--jobs", 64)
        frames = _int_flag("--frames", 600)
        workers = _int_flag("--workers", 2)
        reps = _int_flag("--reps", 3)
        record = sched_bench(jobs=jobs, frames=frames, workers=workers, reps=reps)
        record["command"] = (
            f"python bench.py --sched --jobs {jobs} --frames {frames} "
            f"--workers {workers} --reps {reps}"
        )
        print(json.dumps(record))
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "results",
            "SCHED_BENCH.json",
        )
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        return 0

    if "--ha" in sys.argv:
        total_workers = _int_flag("--workers", 32)
        jobs = _int_flag("--jobs", 12)
        frames = _int_flag("--frames", 100)
        reps = _int_flag("--reps", 5)
        record = ha_shard_bench(
            total_workers=total_workers, jobs=jobs, frames=frames, reps=reps
        )
        record["command"] = (
            f"python bench.py --ha --workers {total_workers} --jobs {jobs} "
            f"--frames {frames} --reps {reps}"
        )
        print(json.dumps(record))
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "results",
            "HA_BENCH.json",
        )
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        return 0

    if "--speculation" in sys.argv:
        workers = _int_flag("--workers", 3)
        frames = _int_flag("--frames", 24)
        reps = _int_flag("--reps", 5)
        record = speculation_bench(workers=workers, frames=frames, reps=reps)
        record["command"] = (
            f"python bench.py --speculation --workers {workers} "
            f"--frames {frames} --reps {reps}"
        )
        print(json.dumps(record))
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "results",
            "SPEC_BENCH.json",
        )
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        return 0

    if "--tile-scaling" in sys.argv:
        reps = _int_flag("--reps", 5)
        record = tile_scaling_bench(reps=reps)
        record["command"] = f"python bench.py --tile-scaling --reps {reps}"
        print(json.dumps(record))
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "results",
            "TILE_BENCH.json",
        )
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        return 0

    if "--bvh-compare" in sys.argv:
        index = sys.argv.index("--bvh-compare")
        deep = (
            sys.argv[index + 1]
            if index + 1 < len(sys.argv) and not sys.argv[index + 1].startswith("-")
            else "03_physics-2-mesh"
        )
        control = _str_flag("--control", "02_physics-mesh")
        frames = _int_flag("--frames", 3)
        reps = _int_flag("--reps", 5)
        bounces = _int_flag("--bounces", BOUNCES)
        record = bvh_compare(
            deep, control, frames=frames, reps=reps, bounces=bounces
        )
        record["command"] = (
            f"python bench.py --bvh-compare {deep} --control {control} "
            f"--frames {frames} --reps {reps} --bounces {bounces}"
        )
        print(json.dumps(record))
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "results",
            "BVH_BENCH.json",
        )
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        return 0

    import jax

    headline_started = time.perf_counter()
    fps = measure_fps()
    platform = jax.devices()[0].platform
    try:
        baseline = cpu_baseline_fps()
        vs_baseline = fps / baseline if baseline > 0 else 0.0
    except Exception as e:  # noqa: BLE001 - bench must still report
        print(f"warning: CPU baseline failed: {e}", file=sys.stderr)
        vs_baseline = 0.0
    record = {
        "metric": f"04_very-simple frames/sec/chip ({WIDTH}x{HEIGHT}, {SAMPLES}spp, {platform})",
        "value": round(fps, 3),
        "unit": "frames/s/chip",
        "vs_baseline": round(vs_baseline, 3),
    }
    try:
        record.update(chip_efficiency(fps, CHUNKS, "04_very-simple"))
    except Exception as e:  # noqa: BLE001 - accounting must not kill the bench
        print(f"warning: chip efficiency accounting failed: {e}", file=sys.stderr)
    # Per-kernel roofline placements captured during this run (any
    # instrumented renderer the timed windows exercised) —
    # obs/profiling.py's view, the same section statistics.json folds
    # from run artifacts.
    from tpu_render_cluster.obs.profiling import get_profiler

    roofline = get_profiler().view()
    if roofline:
        record["roofline"] = roofline
    # Whole-stack attribution over the same process-global registry. A
    # pure-render invocation carries no cluster series and stamps
    # nothing; a colocated run (harness import, instrumented modes) gets
    # the same section statistics.json folds from run artifacts.
    try:
        from tpu_render_cluster.analysis.obs_events import (
            summarize_attribution,
        )
        from tpu_render_cluster.obs import get_registry

        attribution = summarize_attribution(
            [{"metrics": get_registry().snapshot()}],
            worker_seconds=time.perf_counter() - headline_started,
        )
        if attribution:
            record["attribution"] = attribution
    except Exception as e:  # noqa: BLE001 - accounting must not kill the bench
        print(f"warning: attribution accounting failed: {e}", file=sys.stderr)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
