"""Wavefront path tracing: active-ray compaction + bucketed relaunch.

The masked bounce loop (integrator.trace_paths) marches EVERY lane
through every bounce; after bounce 1 most lanes carry dead paths that
still occupy kernel lanes (and, before the live-count prefetch, still
drove BVH packet walks). Wavefront execution fixes the occupancy: after
each bounce the live rays are stream-compacted to the front, the live
count is read back, rounded UP to a small ladder of power-of-two bucket
sizes (the same bucketed-jit idiom as ops/assignment.py — XLA compiles
once per bucket, not per live count), and the next bounce is relaunched
over the compacted bucket only. Radiance scatters back through the
carried ORIGINAL lane ids, which also key the kernels' counter-based
RNG — so a ray's stream is identical whether it rides the masked loop,
the megakernel, or any compacted position here (the RNG-stability
contract that makes masked-vs-wavefront images comparable).

Two cooperating mechanisms, one per execution mode:

- IN-JIT compaction (integrator.trace_paths): the per-bounce Morton
  re-sort already parks dead lanes at the tail; the bounce kernels now
  take a live-count scalar and skip all-dead tail blocks. Shapes stay
  static, so this composes with jit/vmap/shard_map (tile/spp sharding)
  — but the launch width never shrinks.
- HOST-DRIVEN bucketed relaunch (this module): one device sync per
  bounce buys dynamically shrinking launch widths. Runs outside jit, so
  it is a per-frame driver (the worker backend's wavefront mode), not a
  drop-in for the fused renderer.

Instrumented via obs/: ``render_lane_occupancy`` gauge (live / launched
width of the last relaunch), ``render_alive_fraction`` per-bounce
histogram (live / original wavefront — the survival curve bench.py
folds into ``wasted_lane_fraction``), ``render_compiles_total`` counter
(new bucket shapes — the recompile bound the bucketing exists for), and
per-bounce spans on the process tracer (Perfetto-visible).
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp

from tpu_render_cluster.render import pallas_kernels as pk

# Linear bucket bounds for the alive-fraction histogram: fractions live
# in [0, 1], where the default log ladder (1e-4..1e3) has almost no
# resolution. One definition site (like obs.render_fps_gauge) so every
# process files observations into merge-compatible buckets.
ALIVE_FRACTION_BUCKETS = tuple((i + 1) / 16 for i in range(16))


def lane_occupancy_gauge(registry=None):
    """live / launched-width of the most recent wavefront relaunch."""
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.gauge(
        "render_lane_occupancy",
        "Live-lane fraction of the last wavefront bounce launch "
        "(live rays / bucketed launch width)",
    )


def alive_fraction_histogram(registry=None):
    """Per-bounce survival: live rays / original wavefront size."""
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.histogram(
        "render_alive_fraction",
        "Per-bounce live fraction of the original wavefront "
        "(1 - this, averaged, is bench.py's wasted_lane_fraction)",
        labels=("bounce",),
        buckets=ALIVE_FRACTION_BUCKETS,
    )


def launch_occupancy_histogram(registry=None):
    """Per-relaunch live fraction of the LAUNCHED bucket (live / bucket).

    The survival histogram above measures the scene (live / original
    wavefront — what a full-width masked loop wastes); this one measures
    the DRIVER (how much of what it actually launched was live), which
    is what the bucketed reclaim improves and what the ray pool's
    render_pool_live_fraction is compared against in bench.py's
    three-way record. The one-program tier feeds it too for a deep mesh
    scene (worker/backends/tpu_raytrace.py: per bounce, live / the
    frame's whole ray set, which is what each of its launches is handed).
    """
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.histogram(
        "render_launch_occupancy",
        "Per-bounce live fraction of the launched width: the wavefront "
        "driver's bucket (1 - this, averaged, is its own "
        "wasted_lane_fraction), the whole frame in the one-program tier",
        buckets=ALIVE_FRACTION_BUCKETS,
    )


def compile_counter(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_compiles_total",
        "Wavefront programs compiled (first sighting of a (kind, bucket) "
        "shape this process) — grows with the bucket ladder, not frames",
    )


# First-sighting tracker behind render_compiles_total. Python-level on
# purpose: it counts the shapes the drivers have launched (the quantity
# the bucket ladder / fixed pool width bounds), independent of jax cache
# internals. Keyed per DRIVER KIND (wavefront vs raypool) so the two
# drivers' key namespaces can't collide, and resettable so tests can
# assert on compile-count deltas without inheriting another test's
# sightings (tests/conftest.py resets it around every test).
_seen_shapes: dict[str, set[tuple]] = {}


def note_compile(driver: str, *key) -> None:
    """Count a first-sighting of ``key`` for ``driver`` into
    render_compiles_total (idempotent per (driver, key))."""
    seen = _seen_shapes.setdefault(driver, set())
    if key not in seen:
        seen.add(key)
        compile_counter().inc()


def reset_compile_tracking(driver: str | None = None) -> None:
    """Forget first-sightings (one driver kind, or all).

    Test isolation only: the obs counter itself keeps its process-wide
    value (counters are monotonic); resetting merely makes the next
    sighting of a shape count again, so per-test DELTA assertions are
    independent of which shapes earlier tests visited.
    """
    if driver is None:
        _seen_shapes.clear()
    else:
        _seen_shapes.pop(driver, None)


def _count_compile(*key) -> None:
    note_compile("wavefront", *key)


def bucket_for(live: int, cap: int, block: int) -> int:
    """Smallest power-of-two multiple of ``block`` >= ``live``, <= ``cap``.

    The relaunch ladder: block, 2*block, 4*block, ... — at most
    log2(cap / block) + 1 distinct jit shapes per (scene, config), the
    same compile-once-per-bucket idiom as ops/assignment._next_bucket.
    """
    size = block
    while size < live:
        size *= 2
    return min(size, cap)


@jax.jit
def compaction_order(alive):
    """Stable partition permutation via prefix sums: alive lanes first.

    Returns (perm, live) with ``x[perm]`` compacted — live lanes in
    their original relative order, then the dead tail. A cumsum scatter,
    not an argsort: O(n) work and no comparison sort on the hot path.
    """
    alive_i32 = alive.astype(jnp.int32)
    live = jnp.sum(alive_i32)
    front = jnp.cumsum(alive_i32) - 1
    back = live + jnp.cumsum(1 - alive_i32) - 1
    dest = jnp.where(alive, front, back)
    n = alive.shape[0]
    perm = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    return perm, live


@jax.jit
def _compact_sphere(origins, directions, throughput, alive, lane, rng):
    """Compact sphere-scene state (no coherence sort needed — the sphere
    pass has no packet culling, so only the dead/alive partition
    matters). One packed gather so the random-access cost is paid once
    per row, not per field. ``rng`` is the RNG-counter row riding next
    to the scatter index ``lane`` (identical arrays unless the caller
    renders a region with full-frame lane ids — XLA CSEs the duplicate
    gather away in the identical case)."""
    perm, live = compaction_order(alive)
    packed = jnp.concatenate([origins, directions, throughput], axis=1)[perm]
    return (
        packed[:, 0:3],
        packed[:, 3:6],
        packed[:, 6:],  # width-generic: f32 [R, 3] or bf16-packed [R, 2]
        alive[perm],
        lane[perm],
        rng[perm],
        live,
    )


@jax.jit
def _compact_mesh(origins, directions, throughput, alive, lane, rng, mesh):
    """Compact mesh-scene state with the integrator's coherence sort.

    _ray_sort_order's dead flag (bit 31) already parks dead lanes at the
    tail, so the Morton/candidate re-sort IS the compaction permutation
    — one gather buys both packet coherence and the partition.
    """
    from tpu_render_cluster.render.integrator import _ray_sort_order

    order = _ray_sort_order(origins, directions, alive, mesh=mesh)
    packed = jnp.concatenate([origins, directions, throughput], axis=1)[order]
    return (
        packed[:, 0:3],
        packed[:, 3:6],
        packed[:, 6:],
        alive[order],
        lane[order],
        rng[order],
        jnp.sum(alive.astype(jnp.int32)),
    )


@jax.jit
def _compact_mesh_keyed(origins, directions, throughput, alive, lane, rng,
                        keys):
    """Compact mesh-scene state by the PRECOMPUTED coherence key column.

    The TLAS bounce kernels emit the next bounce's sort key from their
    epilogue (pallas_kernels.coherence_key_u32 — dead flag at
    KEY_DEAD_BIT, 29), so
    the re-sort here is one argsort over an int32 column instead of the
    separate XLA broadphase + quantization pass ``_compact_mesh`` pays
    over the full ray state. Same contract: dead lanes to the tail, one
    packed gather for coherence AND partition.
    """
    order = jnp.argsort(keys)
    packed = jnp.concatenate([origins, directions, throughput], axis=1)[order]
    return (
        packed[:, 0:3],
        packed[:, 3:6],
        packed[:, 6:],
        alive[order],
        lane[order],
        rng[order],
        jnp.sum(alive.astype(jnp.int32)),
    )


@jax.jit
def _initial_mesh_keys(origins, directions, alive, mesh):
    """Bounce-0 coherence keys for the TLAS wavefront: the XLA twin of
    the kernel epilogue, via THE shared derivation
    (pallas_kernels.initial_mesh_sort_keys — the deep per-bounce path
    keys through the same site). Frame-dependent, never ray-dependent,
    so every launch of a frame keys identically; bounces > 0 read the
    kernel-emitted column."""
    return pk.initial_mesh_sort_keys(mesh, origins, directions, alive)


@functools.partial(jax.jit, static_argnames=("total_bounces", "quant"))
def _sphere_step(
    scene, origins, directions, throughput, alive, lane, rng, live, seed,
    bounce, radiance_total, *, total_bounces: int, quant: int = 0,
):
    # quant >= 1: the carried throughput column is bf16-packed ([R, 2]
    # f32 words) — the packed-carried-state half of the TRC_BVH_QUANT
    # tier. The kernel still computes in f32; the pack/unpack round-trip
    # per bounce is the divergence tests/test_bvhq.py budgets.
    thr = pk.unpack_throughput_bf16(throughput) if quant else throughput
    contribution, o2, d2, thr2, alive2 = pk.sphere_bounce_pallas(
        scene, origins, directions, thr, alive, seed, bounce,
        total_bounces=total_bounces, lane=rng, live_count=live,
    )
    if quant:
        thr2 = pk.pack_throughput_bf16(thr2)
    return o2, d2, thr2, alive2, radiance_total.at[lane].add(contribution)


@functools.partial(
    jax.jit,
    static_argnames=("total_bounces", "use_tlas", "quant", "tlas_block"),
)
def _mesh_step(
    scene, mesh, origins, directions, throughput, alive, lane, rng, live, seed,
    bounce, radiance_total, *, total_bounces: int, use_tlas: bool = False,
    quant: int = 0, tlas_block: int = 256,
):
    thr = pk.unpack_throughput_bf16(throughput) if quant else throughput
    contribution, o2, d2, thr2, alive2, keys2 = pk.mesh_bounce_pallas(
        scene, mesh, origins, directions, thr, alive, seed, bounce,
        total_bounces=total_bounces, lane=rng, live_count=live,
        use_tlas=use_tlas, quant=quant, tlas_block=tlas_block,
    )
    if quant:
        thr2 = pk.pack_throughput_bf16(thr2)
    return (
        o2, d2, thr2, alive2, radiance_total.at[lane].add(contribution),
        keys2,
    )


def trace_paths_wavefront(
    scene, origins, directions, seed, *, max_bounces: int = 4, mesh=None,
    rng_lanes=None, use_tlas=None, quant=None,
):
    """Trace one sample per ray, wavefront-style; returns radiance [R, 3].

    The host-driven loop: compact -> read live count (ONE device sync
    per bounce — the price of dynamic launch widths) -> round up to a
    bucket -> relaunch the fused bounce kernel over the bucket only ->
    scatter the contribution back through the carried lane ids. An
    all-dead wavefront ends the loop early (remaining bounces cannot
    contribute).

    Physics and per-original-lane RNG streams are identical to the
    masked Pallas paths (integrator.trace_paths with TRC_PALLAS on), so
    images agree up to FP tie-breaking — tests/test_wavefront.py pins
    the equivalence. ``rng_lanes`` (optional [R] int32) overrides the
    RNG counters with FULL-frame lane ids: the cluster-tile region path
    (render_region_wavefront) uses it so a tiled wavefront frame
    reproduces the whole-frame wavefront image on its pixels.
    ``use_tlas`` (None = env tier) selects the two-level mesh kernel
    variant; with it, each bounce's compaction reads the key column the
    previous bounce kernel emitted instead of re-deriving keys.
    """
    from tpu_render_cluster.obs import get_tracer, step

    n0 = origins.shape[0]
    kind = "mesh" if mesh is not None else "sphere"
    tlas = (
        pk.use_tlas_for(mesh.instances.translation.shape[0], use_tlas)
        if mesh is not None else False
    )
    # Node-format tier (None = TRC_BVH_QUANT): quantized node tables in
    # the bounce kernels AND the bf16-packed carried throughput the
    # compaction gathers move — both halves flip together so the A/B
    # bench's variants stay whole.
    quant = pk.bvh_quant_mode() if quant is None else max(0, min(int(quant), 2))
    # The bucket quantum is the kernel's ray block: the TLAS kernels
    # packet at the narrower tlas_block_r, which also buys the ladder
    # finer reclaim granularity.
    tlas_block = pk.tlas_block_r()
    if mesh is None:
        block = pk.SPHERE_BOUNCE_BLOCK_R
    elif tlas:
        block = tlas_block
    else:
        block = pk.BVH_BLOCK_R
    tracer = get_tracer()
    occupancy = lane_occupancy_gauge()
    survival = alive_fraction_histogram()
    launched = launch_occupancy_histogram()

    radiance_total = jnp.zeros((n0, 3), jnp.float32)
    throughput = jnp.ones((n0, 3), jnp.float32)
    if quant:
        throughput = pk.pack_throughput_bf16(throughput)
    alive = jnp.ones((n0,), bool)
    lane = jnp.arange(n0, dtype=jnp.int32)
    rng = lane if rng_lanes is None else jnp.asarray(rng_lanes, jnp.int32)
    seed = jnp.asarray(seed, jnp.int32)
    keys = _initial_mesh_keys(origins, directions, alive, mesh) if tlas else None

    for bounce in range(max_bounces):
        start_wall = time.time()
        start_mono = time.perf_counter()
        width = origins.shape[0]
        _count_compile(kind, "compact", width)
        if tlas:
            origins, directions, throughput, alive, lane, rng, live_dev = (
                _compact_mesh_keyed(
                    origins, directions, throughput, alive, lane, rng, keys
                )
            )
        elif mesh is not None:
            origins, directions, throughput, alive, lane, rng, live_dev = (
                _compact_mesh(
                    origins, directions, throughput, alive, lane, rng, mesh
                )
            )
        else:
            origins, directions, throughput, alive, lane, rng, live_dev = (
                _compact_sphere(
                    origins, directions, throughput, alive, lane, rng
                )
            )
        # The bounce's host sync, a frame step of its own: it suspends the
        # caller's dispatch step while the host waits for the live count.
        with step("device_wait"):
            live = int(live_dev)
        survival.observe(live / n0, bounce=bounce)
        if live == 0:
            occupancy.set(0.0)
            tracer.complete(
                "wavefront_bounce", cat="render", start_wall=start_wall,
                duration=time.perf_counter() - start_mono,
                track="wavefront",
                args={"bounce": bounce, "live": 0, "bucket": 0,
                      "alive_fraction": 0.0},
            )
            break
        bucket = bucket_for(live, cap=width, block=block)
        if bucket < width:
            origins = origins[:bucket]
            directions = directions[:bucket]
            throughput = throughput[:bucket]
            alive = alive[:bucket]
            lane = lane[:bucket]
            rng = rng[:bucket]
        occupancy.set(live / bucket)
        launched.observe(live / bucket)
        _count_compile(kind, "bounce", bucket, max_bounces, tlas, quant)
        # Roofline profiling: the bucket program's identity is (kind,
        # bucket, bounces, node format) — the same identity the
        # bucketed-jit cache compiles per. The capture args are stashed
        # BEFORE the step reassigns them, but the lowering itself runs
        # after the bounce's duration stamp so it never inflates a
        # measured bounce. The builder/wide dims tag which BLAS build the
        # mesh passed in carries (callers building a non-default tree
        # pass env overrides through scene_mesh_set, so the env tiers
        # describe it).
        from tpu_render_cluster.obs.profiling import (
            bvh_dims,
            get_profiler,
            kernel_key,
        )
        from tpu_render_cluster.render.mesh import bvh_builder, bvh_wide

        profiler = get_profiler()
        step_key = kernel_key(
            f"wavefront_{kind}_bounce", None, bucket=bucket, b=max_bounces,
            **bvh_dims(tlas=tlas, quant=quant, builder=bvh_builder(),
                       wide=bvh_wide()),
        )
        capture_args = None
        if not profiler.captured(step_key):
            capture_args = (
                (scene, mesh, origins, directions, throughput, alive, lane,
                 rng, live_dev, seed, bounce, radiance_total)
                if mesh is not None
                else (scene, origins, directions, throughput, alive, lane,
                      rng, live_dev, seed, bounce, radiance_total)
            )
        if mesh is not None:
            (origins, directions, throughput, alive, radiance_total,
             keys) = _mesh_step(
                scene, mesh, origins, directions, throughput, alive,
                lane, rng, live_dev, seed, bounce, radiance_total,
                total_bounces=max_bounces, use_tlas=tlas, quant=quant,
                tlas_block=tlas_block,
            )
        else:
            origins, directions, throughput, alive, radiance_total = (
                _sphere_step(
                    scene, origins, directions, throughput, alive, lane,
                    rng, live_dev, seed, bounce, radiance_total,
                    total_bounces=max_bounces, quant=quant,
                )
            )
        bounce_seconds = time.perf_counter() - start_mono
        # Measured-time pairing for the roofline view: the host-driven
        # loop syncs once per bounce, so the bounce wall time (compact +
        # live-count sync + step dispatch) is the tier's honest per-launch
        # cost — there is no tighter device fence to pair with.
        profiler.record_execute(step_key, bounce_seconds)
        if capture_args is not None:
            if mesh is not None:
                profiler.capture(
                    step_key, _mesh_step, *capture_args,
                    total_bounces=max_bounces, use_tlas=tlas, quant=quant,
                    tlas_block=tlas_block,
                )
            else:
                profiler.capture(
                    step_key, _sphere_step, *capture_args,
                    total_bounces=max_bounces, quant=quant,
                )
        tracer.complete(
            "wavefront_bounce", cat="render", start_wall=start_wall,
            duration=bounce_seconds,
            track="wavefront",
            args={"bounce": bounce, "live": live, "bucket": bucket,
                  "alive_fraction": round(live / n0, 4)},
        )
    return radiance_total


@functools.partial(
    jax.jit, static_argnames=("width", "height", "samples")
)
def _frame_rays(camera, frame, *, width: int, height: int, samples: int):
    """Primary rays for a full frame, samples flattened onto the ray axis.

    Built from render_tile's OWN helper (integrator.frame_rays_and_seed,
    also the ray-pool driver's source), so a wavefront frame and a
    masked frame provably trace the same physical rays with the same
    per-lane RNG streams — the derivation cannot drift.
    """
    from tpu_render_cluster.render.integrator import frame_rays_and_seed

    return frame_rays_and_seed(
        camera, frame, width=width, height=height, samples=samples
    )


@functools.partial(jax.jit, static_argnames=("samples", "height", "width"))
def _finish_frame(radiance, *, samples: int, height: int, width: int):
    n = height * width
    return radiance.reshape(samples, n, 3).mean(axis=0).reshape(
        height, width, 3
    )


def render_frame_wavefront(
    scene_name: str,
    frame_index,
    *,
    width: int = 512,
    height: int = 512,
    samples: int = 8,
    max_bounces: int = 4,
    use_tlas=None,
    quant=None,
):
    """Render one frame through the wavefront driver; [H, W, 3] linear.

    The wavefront counterpart of integrator.render_frame /
    fused_frame_renderer. Not a single fused dispatch — the driver's
    per-bounce host sync is the mechanism — so scene/camera build runs
    eagerly; that cost is noise on the deep-walk scenes this mode is
    for.
    """
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene(scene_name, frame_index)
    camera = scene_camera(scene_name, frame_index)
    mesh = scene_mesh_set(scene_name, frame_index)
    origins, directions, seed = _frame_rays(
        camera, jnp.asarray(frame_index, jnp.float32),
        width=width, height=height, samples=samples,
    )
    radiance = trace_paths_wavefront(
        scene, origins, directions, seed, max_bounces=max_bounces, mesh=mesh,
        use_tlas=use_tlas, quant=quant,
    )
    return _finish_frame(
        radiance, samples=samples, height=height, width=width
    )


@functools.partial(
    jax.jit,
    static_argnames=("width", "height", "samples", "tile_height", "tile_width"),
)
def _region_rays(
    camera, frame, y0, x0, *, width: int, height: int, samples: int,
    tile_height: int, tile_width: int,
):
    from tpu_render_cluster.render.integrator import region_rays_and_seed

    return region_rays_and_seed(
        camera, frame, width=width, height=height, samples=samples,
        y0=y0, x0=x0, tile_height=tile_height, tile_width=tile_width,
    )


def render_region_wavefront(
    scene_name: str,
    frame_index,
    *,
    y0: int,
    x0: int,
    tile_height: int,
    tile_width: int,
    width: int = 512,
    height: int = 512,
    samples: int = 8,
    max_bounces: int = 4,
    use_tlas=None,
    quant=None,
):
    """Render one region of a frame through the wavefront driver.

    The cluster-tile counterpart of ``render_frame_wavefront``: region
    rays + full-frame RNG lane ids (integrator.region_rays_and_seed), so
    a stitched grid of regions reproduces the whole-frame wavefront
    image — the worker's wavefront tier serves tile work units through
    here. Returns [tile_height, tile_width, 3] linear radiance.
    """
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene(scene_name, frame_index)
    camera = scene_camera(scene_name, frame_index)
    mesh = scene_mesh_set(scene_name, frame_index)
    origins, directions, lanes, seed = _region_rays(
        camera, jnp.asarray(frame_index, jnp.float32),
        jnp.asarray(y0, jnp.int32), jnp.asarray(x0, jnp.int32),
        width=width, height=height, samples=samples,
        tile_height=tile_height, tile_width=tile_width,
    )
    radiance = trace_paths_wavefront(
        scene, origins, directions, seed, max_bounces=max_bounces,
        mesh=mesh, rng_lanes=lanes, use_tlas=use_tlas, quant=quant,
    )
    return _finish_frame(
        radiance, samples=samples, height=tile_height, width=tile_width
    )


def wavefront_active(scene_name: str, *, backend_flag: str | None = None) -> bool:
    """Whether the wavefront driver should render this scene.

    ``backend_flag`` (the worker's ``--wavefront`` / constructor knob)
    overrides the ``TRC_WAVEFRONT`` env tier. Only ``force`` turns the
    driver on: ``auto`` is the one-program tier for every scene
    (``pk.wavefront_mode`` says why), so the answer never depends on the
    scene's geometry and nothing is built to give it.
    """
    if not pk.pallas_enabled():
        return False
    return pk.tier_forced(backend_flag if backend_flag is not None else pk.wavefront_mode())


def _mean_complement(histogram) -> float | None:
    count = 0
    total = 0.0
    for _key, series in histogram._series_items():
        count += series.count
        total += series.sum
    if count == 0:
        return None
    return 1.0 - total / count


def wasted_lane_fraction(registry=None) -> float | None:
    """1 - mean(alive fraction) over every recorded wavefront bounce.

    The average fraction of the ORIGINAL wavefront that is dead at each
    bounce launch — what a masked full-width bounce loop wastes, and
    what compaction reclaims. None before any wavefront render ran.
    """
    return _mean_complement(alive_fraction_histogram(registry))


def launched_wasted_lane_fraction(registry=None) -> float | None:
    """1 - mean(live / launched bucket) over every wavefront relaunch —
    the waste the wavefront driver itself still pays after the bucketed
    reclaim (block-quantized launches + the unfillable first bounce).
    None before any wavefront render ran."""
    return _mean_complement(launch_occupancy_histogram(registry))
