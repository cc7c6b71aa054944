"""Device-resident ray pool: cross-frame wavefront batching, in-jit
compaction, zero host syncs in the steady state.

The PR-2 wavefront driver (render/compaction.py) buys shrinking launch
widths with ONE DEVICE SYNC PER BOUNCE, and its launch width can only
shrink — dead lanes are reclaimed in block-sized buckets but never
refilled, so on the deep-walk scenes it exists for it still measured
73.7% wasted lanes and a 1.05x win (results/WAVEFRONT_BENCH.json). The
wavefront literature's fix ("Megakernels Considered Harmful": keep a
persistent ray queue saturated; "Data Parallel Path Tracing in Object
Space": decouple the work unit from the image) is to make the pool
DEVICE-RESIDENT and CONTINUOUSLY REFILLED: lanes freed by frame i's
dead paths are immediately reloaded with frame i+1's next unserved
primary rays, so the kernel never drains and the host never syncs
mid-batch.

Execution shape: ONE jitted program per (scene family, frame-window
cap, image config, pool width) runs a ``lax.while_loop`` over a
fixed-width pool. Each iteration, entirely on device:

1. permutation — dead lanes to the tail; for mesh scenes the
   coherence re-sort (frame id, candidate instance, Morton cell,
   direction octant) FOLDS INTO the same permutation (one argsort key
   with a dead bit, the pool generalization of integrator
   ``_ray_sort_order``); sphere scenes need no coherence and reuse
   ``compaction.compaction_order``'s prefix-sum partition;
2. refill — freed tail slots gather the next unserved primary rays of
   the multi-frame batch (pre-generated in the same program via the
   shared ``integrator.flat_sample_rays`` derivation, so rays and RNG
   provably match the masked per-frame renderer);
3. bounce — ONE pool-mode kernel launch (``pallas_kernels.pool_io``):
   lanes carry ``(frame, original_lane, bounce)`` so the counter PCG
   streams are bit-identical to the masked loop's, and the stacked
   multi-frame scene is masked per lane by frame id;
4. scatter-back — each lane's contribution lands in its own frame's
   buffer at ``frame * rays_per_frame + lane`` regardless of service
   order.

The loop condition (`unserved primaries remain or any lane alive`) and
everything above are device arithmetic: the host blocks exactly once,
at the end of the batch, to fetch the finished frames — one sync per
BATCH instead of one per bounce.

Per-iteration occupancy/refill telemetry is accumulated in fixed-size
device logs carried through the loop and emitted AFTER the batch:
``render_pool_occupancy`` gauge, ``render_pool_live_fraction``
histogram (bench.py's raypool wasted_lane_fraction), refill/iteration
counters, and per-iteration Perfetto spans on a dedicated "raypool"
track. Span timing within a batch is synthetic (the batch wall time
split evenly — the device never told the host when iterations
happened; that is the point), flagged ``synthetic_timing`` in args;
occupancy/refill args are real device-measured values.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from tpu_render_cluster.utils.env import env_int, env_str
from tpu_render_cluster.render import pallas_kernels as pk
from tpu_render_cluster.render.compaction import (
    ALIVE_FRACTION_BUCKETS,
    compaction_order,
    note_compile,
)

# Fixed length of the per-iteration device telemetry logs carried through
# the while loop. Batches that somehow exceed it keep rendering correctly
# (late iterations overwrite the last slot); only telemetry truncates, and
# the emitter flags it.
RAYPOOL_LOG_CAP = 2048

# Hard ceiling on the frame-window cap (the sort key holds 5 frame-id
# bits, and the stacked-scene sweep cost grows with the window).
RAYPOOL_MAX_FRAMES = 32


def raypool_mode() -> str:
    """The ``TRC_RAYPOOL`` env tier: ``off`` / ``auto`` / ``force``.

    - unset (``auto``): never, whatever the queue holds. With four deep
      mesh frames queued the pool read 0.7674 frames/s on the chip
      (ledger PR 25, ``03ph2mesh-1w-queued``) where the one-program
      tier reads 1.368 on the same frames (builder's runs, PERF.md §6,
      PR 27);
    - ``TRC_RAYPOOL=0`` (also ``false``/``off``/``no``): never;
    - anything else truthy: force it for every Pallas-rendered scene,
      single frames and spheres included.
    """
    value = (env_str("TRC_RAYPOOL") or "").strip().lower()
    if value in ("", "auto"):
        return "auto"
    if value in ("0", "false", "off", "no"):
        return "off"
    return "force"


def raypool_frame_cap() -> int:
    """Frames per compiled pool window (``TRC_RAYPOOL_FRAMES``, default 8).

    A COMPILE-TIME capacity, not the batch size: any batch of 1..cap
    frames reuses the same compiled program (the served-ray total is a
    traced scalar), and larger batches chunk into windows of this size
    (one host sync per window). Clamped to [1, RAYPOOL_MAX_FRAMES].
    """
    cap = env_int("TRC_RAYPOOL_FRAMES", 8)
    return max(1, min(cap, RAYPOOL_MAX_FRAMES))


def raypool_width(rays_per_frame: int, block: int) -> int:
    """Pool width: ``TRC_RAYPOOL_WIDTH`` or one frame's rays, block-
    rounded and clamped to [1, 64] blocks. Part of the pool config (a
    distinct width is a distinct compile), independent of batch size."""
    width = env_int(
        "TRC_RAYPOOL_WIDTH", min(rays_per_frame, 64 * block)
    )
    return max(block, -(-width // block) * block)


def raypool_active(
    scene_name: str,
    *,
    backend_flag: str | None = None,
    frames_ahead: int = 0,
) -> bool:
    """Whether the ray-pool driver should render this workload.

    ``backend_flag`` (the worker's ``--raypool`` / constructor knob)
    overrides the ``TRC_RAYPOOL`` env tier. Only ``force`` turns the pool
    on: under ``auto`` it is off for every scene and every
    ``frames_ahead`` — frames queued ahead no longer engage it
    (``raypool_mode`` says why) — so nothing of the scene is built to
    answer.
    """
    if not pk.pallas_enabled():
        return False
    return pk.tier_forced(backend_flag if backend_flag is not None else raypool_mode())


# -- obs ---------------------------------------------------------------------


def pool_occupancy_gauge(registry=None):
    """Mean live-lane fraction of the pool over the last batch."""
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.gauge(
        "render_pool_occupancy",
        "Mean live fraction of the ray pool across the last batch's "
        "iterations (live lanes / pool width)",
    )


def pool_live_fraction_histogram(registry=None):
    """Per-iteration pool live fraction (1 - mean = wasted lanes)."""
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.histogram(
        "render_pool_live_fraction",
        "Per-iteration live fraction of the LAUNCHED pool width (live "
        "prefix rounded up to whole blocks; skipped all-dead tail "
        "blocks don't count — the same basis as the wavefront driver's "
        "live/bucket). 1 - this, averaged, is the raypool "
        "wasted_lane_fraction",
        buckets=ALIVE_FRACTION_BUCKETS,
    )


def pool_refill_counter(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_pool_refill_rays_total",
        "Primary rays loaded into freed pool lanes (the cross-frame "
        "refill the ray pool exists for)",
    )


def pool_launched_lanes_counter(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_pool_launched_lanes_total",
        "Lanes launched: the pool's live prefix rounded up to whole "
        "blocks, summed over iterations (the denominator of the "
        "lane-weighted raypool wasted_lane_fraction); in the one-program "
        "tier, a deep mesh frame's whole ray set per bounce",
    )


def pool_live_lanes_counter(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_pool_live_lanes_total",
        "Live lanes at launch, summed over iterations (one-program "
        "tier: over bounces) — the numerator of the lane-weighted "
        "occupancy",
    )


def pool_iteration_counter(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_pool_iterations_total",
        "Ray-pool while-loop iterations (one fused "
        "sort+refill+bounce+scatter step per iteration, no host sync)",
    )


def raypool_wasted_lane_fraction(registry=None) -> float | None:
    """Lane-weighted: total dead launched lanes / total launched lanes.

    The raypool analog of compaction.wasted_lane_fraction — the actual
    fraction of launched pool lanes that carried no live ray, aggregated
    over every iteration of every batch. Lane-weighted (counter-based),
    NOT a mean of per-iteration ratios: the drain tail's tiny launches
    have big ratios but near-zero cost, and must not dominate the
    record. None before any pool batch ran.
    """
    launched = pool_launched_lanes_counter(registry).value()
    if launched <= 0:
        return None
    return 1.0 - pool_live_lanes_counter(registry).value() / launched


# -- the device program ------------------------------------------------------


def _dilate4(v):
    """Spread a 4-bit value to every 3rd bit (Morton dilation, readable
    bit-by-bit form — only 4 bits, so cleverness buys nothing)."""
    return (
        ((v >> 0) & jnp.uint32(1))
        | (((v >> 1) & jnp.uint32(1)) << 3)
        | (((v >> 2) & jnp.uint32(1)) << 6)
        | (((v >> 3) & jnp.uint32(1)) << 9)
    )


def _pool_sort_order(origins, directions, alive, fid, lo_w, hi_w):
    """One permutation = compaction AND coherence for the mesh pool.

    Key layout (LSB→MSB): direction octant [0:3), Morton cell of
    origin+direction [3:15), candidate instance [15:25), frame id
    [25:30), dead flag bit 30. Dead lanes sort to the tail (the live-
    count block-skip contract); live lanes group by frame FIRST — a
    frame-pure block top-level-culls every other frame's instances —
    then pack into candidate/Morton-coherent packets exactly like the
    integrator's per-bounce re-sort. One stable argsort, so the
    original relative order breaks ties and the permutation composes
    with the refill's contiguous free tail.
    """
    candidate = pk.instance_entry_candidates(
        origins, directions, lo_w, hi_w
    ).astype(jnp.uint32)
    candidate = jnp.minimum(candidate, jnp.uint32(1023))
    point = origins + directions
    lo = jnp.min(point, axis=0)
    span = jnp.maximum(jnp.max(point, axis=0) - lo, 1e-6)
    cell = ((point - lo) / span * 15.999).astype(jnp.uint32)  # 4 bits/axis
    morton = (
        _dilate4(cell[:, 0])
        | (_dilate4(cell[:, 1]) << 1)
        | (_dilate4(cell[:, 2]) << 2)
    )
    octant = (
        (directions[:, 0] > 0).astype(jnp.uint32)
        | ((directions[:, 1] > 0).astype(jnp.uint32) << 1)
        | ((directions[:, 2] > 0).astype(jnp.uint32) << 2)
    )
    fid_bits = jnp.minimum(fid.astype(jnp.uint32), jnp.uint32(31))
    dead = (~alive).astype(jnp.uint32) << 30
    key = (
        octant
        | (morton << 3)
        | (candidate << 15)
        | (fid_bits << 25)
        | dead
    )
    return jnp.argsort(key)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scene_name", "width", "height", "samples", "max_bounces",
        "pool_width", "tile_shape", "use_tlas", "tlas_leaf", "tlas_block",
        "quant", "builder", "wide",
    ),
)
def _raypool_batch(
    scene_name: str,
    frames,  # [f_cap] float32 frame indices (tail-padded)
    n_frames,  # traced int32: frames actually served (<= f_cap)
    y0,  # traced int32 region origin (0 for whole frames)
    x0,
    *,
    width: int,
    height: int,
    samples: int,
    max_bounces: int,
    pool_width: int,
    tile_shape: tuple[int, int] | None = None,
    use_tlas: bool = True,
    tlas_leaf: int = 4,
    tlas_block: int = 256,
    quant: int = 0,
    builder: str = "sah",
    wide: int = 4,
):
    """The whole batch as ONE compiled program; returns
    (linear images [f_cap, H, W, 3], stats tuple).

    Everything here — primary-ray generation, the stacked multi-frame
    scene, the pool while-loop, per-frame averaging — lives in one XLA
    program. ``n_frames`` is TRACED, so one compile serves every batch
    size up to the window cap (the recompile bound the fixed pool width
    exists for). Every BVH env tier arrives RESOLVED as a static arg
    (``render_batch_raypool`` reads the env outside the trace — the
    env-tiers lint contract); ``quant`` >= 1 additionally packs the
    carried pool state (bf16 throughput words + one fid/bounce/dead meta
    column replacing three), shrinking the bytes the per-iteration
    permutation moves.
    """
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.integrator import (
        frame_rays_and_seed,
        region_rays_and_seed,
    )
    from tpu_render_cluster.render.mesh import cached_mesh_bvh
    from tpu_render_cluster.render.scene import (
        build_mesh_instances,
        build_scene,
        mesh_kind_for_scene,
    )

    f_cap = frames.shape[0]
    if tile_shape is None:
        tile_height, tile_width = height, width
    else:
        tile_height, tile_width = tile_shape
    n = samples * tile_height * tile_width  # rays per frame (of this region)
    total = n_frames * n  # traced: primaries to serve
    pool = pool_width
    block = (
        pk.BVH_BLOCK_R
        if mesh_kind_for_scene(scene_name) is not None
        else pk.SPHERE_BOUNCE_BLOCK_R
    )

    # Primary rays + per-frame trace seeds, via the SAME helpers the
    # masked render_tile / region path use — the RNG/ray derivation
    # cannot drift. Under a region, each lane additionally maps to its
    # FULL-frame lane id (the RNG counter), so a tiled pool batch
    # reproduces the whole-frame streams on its pixels. The tile ORIGIN
    # (y0/x0) is traced — like the other two tiers, one compiled pool
    # program per tile SHAPE serves every position of the grid.
    glane_map = None
    if tile_shape is None:
        def frame_rays(frame):
            return frame_rays_and_seed(
                scene_camera(scene_name, frame), frame,
                width=width, height=height, samples=samples,
            )
    else:
        def frame_rays(frame):
            o, d, _lanes, seed = region_rays_and_seed(
                scene_camera(scene_name, frame), frame,
                width=width, height=height, samples=samples,
                y0=y0, x0=x0, tile_height=tile_height,
                tile_width=tile_width,
            )
            return o, d, seed

        # The local->full-frame lane map is frame-independent (every
        # frame serves the same region); in-graph arithmetic off the
        # traced origin, THE shared derivation (integrator.region_lane_map
        # — the same one region_rays_and_seed builds its lanes from).
        from tpu_render_cluster.render.integrator import region_lane_map

        glane_map = region_lane_map(
            y0=y0, x0=x0, tile_height=tile_height, tile_width=tile_width,
            width=width, height=height, samples=samples,
        )

    prim_o, prim_d, seeds = jax.vmap(frame_rays)(frames)
    prim_o = prim_o.reshape(f_cap * n, 3)
    prim_d = prim_d.reshape(f_cap * n, 3)

    # Stacked multi-frame scene: frame f's spheres carry fid f. The
    # lighting rows are frame-invariant by construction (build_scene's
    # _default_lighting) — take frame 0's.
    scenes = jax.vmap(lambda f: build_scene(scene_name, f))(frames)
    n_spheres = scenes.radii.shape[1]
    sphere_fid = jnp.repeat(jnp.arange(f_cap, dtype=jnp.int32), n_spheres)
    sphere_ops = pk.pool_sphere_operands(
        scenes.centers.reshape(-1, 3),
        scenes.radii.reshape(-1),
        scenes.albedo.reshape(-1, 3),
        scenes.emission.reshape(-1, 3),
        sphere_fid,
        scenes.sun_direction[0], scenes.sun_color[0],
        scenes.sky_horizon[0], scenes.sky_zenith[0],
        scenes.plane_albedo_a[0], scenes.plane_albedo_b[0],
    )

    mesh_kind = mesh_kind_for_scene(scene_name)
    tlas = False
    if mesh_kind is not None:
        # Shared topology, host-cached; the build knobs arrive resolved.
        bvh = cached_mesh_bvh(mesh_kind, builder, wide)
        inst = jax.vmap(lambda f: build_mesh_instances(scene_name, f))(
            frames
        )
        k = inst.translation.shape[1]
        mesh_ops = pk.PoolMeshOperands(
            spheres=sphere_ops,
            sun_direction=scenes.sun_direction[0],
            rotation=inst.rotation.reshape(-1, 3, 3),
            translation=inst.translation.reshape(-1, 3),
            scale=inst.scale.reshape(-1),
            inst_albedo=inst.albedo.reshape(-1, 3),
            ifid=jnp.repeat(jnp.arange(f_cap, dtype=jnp.int32), k),
            k_per_frame=k,
            v0=bvh.v0, e1=bvh.e1, e2=bvh.e2, normal=bvh.normal,
            bounds_min=bvh.bounds_min, bounds_max=bvh.bounds_max,
            skip=bvh.skip, first=bvh.first, count=bvh.count,
            octant=bvh.octant,
        )
        # ``use_tlas`` is a static resolved REQUEST; the actual decision
        # folds in the per-frame instance count, all concrete at trace
        # time (small fields degenerate to the flat sweep — the same
        # rule as pk.use_tlas_for, inlined so no env tier is read inside
        # this traced function).
        tlas = bool(use_tlas) and k > tlas_leaf
        if tlas:
            # The TLAS kernels packet at their own narrower block; it
            # always divides BVH_BLOCK_R, so the BVH_BLOCK_R-rounded
            # pool width stays valid and the launched-lane accounting
            # below matches the kernel's actual skip granularity.
            block = tlas_block
        if not tlas:
            # Sort-key broadphase over SLOT-UNION AABBs: slot k's world
            # AABB unioned across the window's frames, so the candidate
            # pass is [P, K] instead of [P, K*F] (measured ~126
            # ms/iteration of pure glue at F=8 on CPU). The candidate
            # only steers packing — fid sits ABOVE it in the key, so
            # within a frame group the union box is a slightly dilated
            # version of the frame's own box. The TLAS pool needs none
            # of this: its sort reads the key column the bounce kernel
            # emitted.
            inst_lo, inst_hi = pk.pool_instance_aabbs(mesh_ops)
            inst_lo = inst_lo.reshape(f_cap, k, 3).min(axis=0)
            inst_hi = inst_hi.reshape(f_cap, k, 3).max(axis=0)
    else:
        mesh_ops = None

    # Pool state. Unfilled lanes start dead with guaranteed-miss rays
    # (far origin, unit direction) so they can never degenerate a slab
    # test, and fid/lane 0 so their zero contributions scatter harmlessly.
    # quant >= 1 carries the PACKED tuple: throughput as bf16 words
    # ([pool, 2] f32) and ONE meta column (fid | bounce | dead) in place
    # of the separate alive/fid/bounce columns — the alive column is
    # dropped outright (it is the meta dead bit), so the per-iteration
    # permutation gathers 11 words per lane instead of 13 + a bool.
    packed_state = quant >= 1
    state = dict(
        o=jnp.full((pool, 3), 1e7, jnp.float32),
        d=jnp.broadcast_to(
            jnp.array([0.0, 1.0, 0.0], jnp.float32), (pool, 3)
        ),
        lane=jnp.zeros((pool,), jnp.int32),
        served=jnp.int32(0),
        it=jnp.int32(0),
        radiance=jnp.zeros((f_cap * n, 3), jnp.float32),
        occ_log=jnp.zeros((RAYPOOL_LOG_CAP,), jnp.float32),
        refill_log=jnp.zeros((RAYPOOL_LOG_CAP,), jnp.int32),
        refilled=jnp.int32(0),
        live_sum=jnp.float32(0.0),
        launched_sum=jnp.float32(0.0),
    )
    if packed_state:
        state["thr"] = pk.pack_throughput_bf16(
            jnp.ones((pool, 3), jnp.float32)
        )
        state["meta"] = pk.pack_pool_meta(
            jnp.zeros((pool,), jnp.int32),
            jnp.zeros((pool,), jnp.int32),
            jnp.zeros((pool,), bool),
        )
    else:
        state["thr"] = jnp.ones((pool, 3), jnp.float32)
        state["alive"] = jnp.zeros((pool,), bool)
        state["fid"] = jnp.zeros((pool,), jnp.int32)
        state["bounce"] = jnp.zeros((pool,), jnp.int32)
    if tlas:
        # Carried coherence-key column (the TLAS bounce kernel re-emits
        # it every iteration): every initial lane is dead, so one
        # constant dead-bit key is exact — the first sort is a stable
        # identity and the refill fills the pool head.
        state["key"] = jnp.full(
            (pool,), jnp.int32(1 << pk.KEY_DEAD_BIT), jnp.int32
        )
    # Backstop against a non-terminating loop under a lifecycle bug:
    # every iteration either serves new rays or ages live lanes toward
    # the bounce cap, so this bound is generous.
    iter_cap = (total // pool + 2) * (max_bounces + 1) + 4

    def pool_alive(s):
        if packed_state:
            return pk.unpack_pool_meta(s["meta"])[2]
        return s["alive"]

    def cond(s):
        return (s["it"] < iter_cap) & (
            (s["served"] < total) | jnp.any(pool_alive(s))
        )

    def body(s):
        if packed_state:
            s_fid, s_bounce, s_alive = pk.unpack_pool_meta(s["meta"])
        else:
            s_fid, s_bounce, s_alive = s["fid"], s["bounce"], s["alive"]
        # 1. One permutation: dead to the tail (+ frame/candidate/Morton
        # coherence for mesh scenes). The TLAS pool sorts by the key
        # column the previous iteration's bounce kernel emitted (dead
        # flag at pk.KEY_DEAD_BIT, fid above Morton — the same
        # live-grouping the flat key builds, minus the separate
        # broadphase pass).
        if mesh_ops is not None and tlas:
            perm = jnp.argsort(s["key"])
        elif mesh_ops is not None:
            perm = _pool_sort_order(
                s["o"], s["d"], s_alive, s_fid, inst_lo, inst_hi
            )
        else:
            perm, _ = compaction_order(s_alive)
        packed = jnp.concatenate([s["o"], s["d"], s["thr"]], axis=1)[perm]
        o, d = packed[:, 0:3], packed[:, 3:6]
        thr = packed[:, 6:]  # carried form: [P, 3] f32 or [P, 2] packed
        lane = s["lane"][perm]
        if packed_state:
            fid, bounce, alive = pk.unpack_pool_meta(s["meta"][perm])
        else:
            alive = s_alive[perm]
            fid = s_fid[perm]
            bounce = s_bounce[perm]
        live = jnp.sum(alive.astype(jnp.int32))

        # 2. Refill the freed tail with the next unserved primaries.
        take = jnp.minimum(pool - live, total - s["served"])
        slot = jnp.arange(pool, dtype=jnp.int32)
        src = jnp.clip(s["served"] + slot - live, 0, f_cap * n - 1)
        is_new = (slot >= live) & (slot < live + take)
        o = jnp.where(is_new[:, None], prim_o[src], o)
        d = jnp.where(is_new[:, None], prim_d[src], d)
        if packed_state:
            thr = jnp.where(
                is_new[:, None],
                pk.pack_throughput_bf16(jnp.ones((1, 3), jnp.float32)),
                thr,
            )
        else:
            thr = jnp.where(is_new[:, None], 1.0, thr)
        alive = alive | is_new
        new_fid = src // n
        fid = jnp.where(is_new, new_fid, fid)
        lane = jnp.where(is_new, src - new_fid * n, lane)
        bounce = jnp.where(is_new, 0, bounce)
        live2 = live + take

        # 3. One fused bounce over the live prefix (per-lane frame seed
        # + bounce depth key the RNG; all-dead tail blocks skip). Under a
        # region the RNG counter is the lane's FULL-frame id, not its
        # local scatter index. The kernel computes in f32 either way;
        # packed mode converts at the launch boundary.
        seed_row = seeds[jnp.clip(fid, 0, f_cap - 1)]
        rng = (
            lane if glane_map is None
            else glane_map[jnp.clip(lane, 0, n - 1)]
        )
        thr_f32 = pk.unpack_throughput_bf16(thr) if packed_state else thr
        if mesh_ops is not None:
            contrib, o, d, thr_f32, alive_k, key2 = pk.pool_mesh_bounce(
                mesh_ops, o, d, thr_f32, alive, rng, fid, seed_row,
                bounce, live2, total_bounces=max_bounces, use_tlas=tlas,
                tlas_leaf=tlas_leaf, tlas_block=tlas_block, quant=quant,
            )
        else:
            contrib, o, d, thr_f32, alive_k = pk.pool_sphere_bounce(
                sphere_ops, o, d, thr_f32, alive, rng, fid, seed_row,
                bounce, live2, total_bounces=max_bounces,
            )
            key2 = None
        thr = (
            pk.pack_throughput_bf16(thr_f32) if packed_state else thr_f32
        )

        # 4. Scatter-back into each lane's own frame buffer. Dead lanes
        # contribute exact zeros (alive-masked kernel math / skipped
        # blocks), so their stale indices are harmless. unique_indices
        # holds by construction: every (frame, lane) id is served into
        # exactly one pool slot and carried (live or stale) until that
        # slot is refilled with a NEVER-REUSED fresh id — so no two
        # slots ever hold the same id, and XLA may vectorize the scatter
        # instead of serializing it (a real cost on CPU).
        radiance = s["radiance"].at[fid * n + lane].add(
            contrib, unique_indices=True
        )

        # 5. Lifecycle + telemetry. Occupancy is measured against the
        # LAUNCHED width (live prefix rounded up to whole blocks — the
        # all-dead tail blocks beyond it skip the bounce and cost ~0),
        # the same basis as the wavefront driver's live/bucket, so the
        # three modes' wasted_lane_fraction records compare like for
        # like. live_sum tracks pool FULLNESS (live / pool width) for
        # the occupancy gauge.
        bounce = bounce + 1
        alive = alive_k & (bounce < max_bounces)
        log_at = jnp.minimum(s["it"], RAYPOOL_LOG_CAP - 1)
        launched = ((live2 + block - 1) // block) * block
        occupancy = live2.astype(jnp.float32) / jnp.maximum(launched, 1)
        next_state = dict(
            o=o, d=d, thr=thr, lane=lane,
            served=s["served"] + take,
            it=s["it"] + 1,
            radiance=radiance,
            occ_log=s["occ_log"].at[log_at].set(occupancy),
            refill_log=s["refill_log"].at[log_at].set(take),
            refilled=s["refilled"] + take,
            live_sum=s["live_sum"] + live2.astype(jnp.float32),
            launched_sum=s["launched_sum"] + launched.astype(jnp.float32),
        )
        if packed_state:
            next_state["meta"] = pk.pack_pool_meta(fid, bounce, alive)
        else:
            next_state["alive"] = alive
            next_state["fid"] = fid
            next_state["bounce"] = bounce
        if tlas:
            # The kernel keyed lanes by its OWN post-bounce alive; the
            # bounce-cap kill above happens out here, so stamp the dead
            # bit onto capped lanes or the next sort would keep funding
            # their packets instead of reclaiming them.
            next_state["key"] = jnp.where(
                alive, key2, key2 | jnp.int32(1 << pk.KEY_DEAD_BIT)
            )
        return next_state

    final = jax.lax.while_loop(cond, body, state)
    images = (
        final["radiance"]
        .reshape(f_cap, samples, tile_height * tile_width, 3)
        .mean(axis=1)
        .reshape(f_cap, tile_height, tile_width, 3)
    )
    stats = (
        final["it"], final["served"], final["refilled"],
        final["live_sum"], final["launched_sum"],
        final["occ_log"], final["refill_log"],
    )
    return images, stats


# -- host driver -------------------------------------------------------------


def _emit_batch_obs(
    *, scene_name, n_chunk_frames, pool, start_wall, duration,
    iterations, served, refilled, live_sum, launched_sum, occ_log,
    refill_log,
):
    """Feed registry + tracer from one batch's device-side telemetry.

    Per-iteration span timing is SYNTHETIC (batch wall time divided
    evenly — the device never reported per-iteration times, which is
    the whole point of the sync-free loop) and flagged as such;
    occupancy/refill span args are real device measurements.
    """
    from tpu_render_cluster.obs import get_tracer

    tracer = get_tracer()
    logged = min(iterations, RAYPOOL_LOG_CAP)
    histogram = pool_live_fraction_histogram()
    for i in range(logged):
        histogram.observe(float(occ_log[i]))
    if iterations:
        pool_occupancy_gauge().set(live_sum / (iterations * pool))
    pool_refill_counter().inc(refilled)
    pool_iteration_counter().inc(iterations)
    pool_launched_lanes_counter().inc(launched_sum)
    pool_live_lanes_counter().inc(live_sum)

    # Iteration spans first, batch span last: the trace-invariant checker
    # (obs/validate) requires non-decreasing span ends per track in append
    # order, and the iterations end inside the batch window.
    if logged:
        step = duration / logged
        for i in range(logged):
            tracer.complete(
                "raypool_iteration", cat="render",
                start_wall=start_wall + i * step, duration=step,
                track="raypool",
                args={
                    "iteration": i,
                    "occupancy": round(float(occ_log[i]), 4),
                    "refilled": int(refill_log[i]),
                    "synthetic_timing": True,
                },
            )
    tracer.complete(
        "raypool_batch", cat="render", start_wall=start_wall,
        duration=duration, track="raypool",
        args={
            "scene": scene_name,
            "frames": n_chunk_frames,
            "iterations": iterations,
            "rays_served": served,
            "rays_refilled": refilled,
            "pool_width": pool,
            "occupancy_mean": (
                round(live_sum / (iterations * pool), 4) if iterations else 0.0
            ),
            "log_truncated": iterations > RAYPOOL_LOG_CAP,
        },
    )


def render_batch_raypool(
    scene_name: str,
    frame_indices,
    *,
    width: int = 512,
    height: int = 512,
    samples: int = 8,
    max_bounces: int = 4,
    pool_width: int | None = None,
    frame_cap: int | None = None,
    region: tuple[int, int, int, int] | None = None,
    use_tlas: bool | None = None,
    quant: int | None = None,
    builder: str | None = None,
    wide: int | None = None,
):
    """Render a batch of frames through the device-resident ray pool.

    Returns a list of linear [H, W, 3] numpy images, one per entry of
    ``frame_indices`` in order. Batches larger than the frame-window
    cap chunk into windows (one host sync per window); every window of
    any size reuses the one compiled program for this pool config.

    ``region`` = (y0, x0, tile_height, tile_width) restricts every frame
    of the batch to ONE tile region (the cluster-tiling work unit): the
    pool serves the region's rays with their full-frame RNG lane ids, so
    the returned [th, tw, 3] images equal the whole-frame pool render's
    pixels on the region. The batch dimension stays FRAMES — a tiled
    multi-frame job batches same-tile units across frames.
    """
    import numpy as np

    from tpu_render_cluster.obs import step
    from tpu_render_cluster.render.scene import mesh_kind_for_scene

    frames = [int(f) for f in frame_indices]
    if not frames:
        return []
    f_cap = frame_cap if frame_cap is not None else raypool_frame_cap()
    f_cap = max(1, min(f_cap, RAYPOOL_MAX_FRAMES))
    if region is not None:
        region = tuple(int(v) for v in region)
        n = samples * region[2] * region[3]
    else:
        n = samples * height * width
    block = (
        pk.BVH_BLOCK_R
        if mesh_kind_for_scene(scene_name) is not None
        else pk.SPHERE_BOUNCE_BLOCK_R
    )
    pool = pool_width if pool_width is not None else raypool_width(n, block)
    pool = max(block, -(-pool // block) * block)
    # Resolve every BVH env tier HERE, outside the traced batch program
    # (the env-tiers lint contract), and thread the concrete values in as
    # static args — they are part of the pool program's identity, its
    # compile-count key, and its roofline row. The tlas tag mirrors the
    # RESOLVED request; kernel selection still auto-degrades tiny
    # instance fields inside the batch program.
    from tpu_render_cluster.obs.profiling import bvh_dims
    from tpu_render_cluster.render.integrator import resolve_bvh_config

    tlas_resolved, quant, builder, wide = resolve_bvh_config(
        use_tlas, quant, builder, wide
    )
    tlas_leaf = pk.tlas_leaf_size()
    tlas_block = pk.tlas_block_r()
    format_dims = bvh_dims(
        tlas=tlas_resolved, quant=quant, builder=builder, wide=wide
    )

    images: list = []
    for start in range(0, len(frames), f_cap):
        chunk = frames[start:start + f_cap]
        padded = chunk + [chunk[-1]] * (f_cap - len(chunk))
        note_compile(
            "raypool", scene_name, width, height, samples, max_bounces,
            pool, f_cap, None if region is None else (region[2], region[3]),
            int(tlas_resolved), quant, builder, wide,
        )
        start_wall = time.time()
        start_mono = time.perf_counter()
        linear, stats = _raypool_batch(
            scene_name,
            jnp.asarray(padded, jnp.float32),
            jnp.int32(len(chunk)),
            jnp.int32(0 if region is None else region[0]),
            jnp.int32(0 if region is None else region[1]),
            width=width, height=height, samples=samples,
            max_bounces=max_bounces, pool_width=pool,
            tile_shape=None if region is None else (region[2], region[3]),
            use_tlas=tlas_resolved, tlas_leaf=tlas_leaf,
            tlas_block=tlas_block, quant=quant, builder=builder,
            wide=wide,
        )
        # THE host sync of the batch: everything before this line is one
        # dispatched XLA program. The wait and the copy are frame steps of
        # their own (they suspend the caller's dispatch step); the images
        # are asked for behind the program, as np.asarray alone would.
        linear.copy_to_host_async()
        with step("device_wait"):
            jax.block_until_ready((linear, stats))
        with step("readback"):
            linear = np.asarray(linear)
            (iterations, served, refilled, live_sum, launched_sum, occ_log,
             refill_log) = (
                int(stats[0]), int(stats[1]), int(stats[2]),
                float(stats[3]), float(stats[4]),
                np.asarray(stats[5]), np.asarray(stats[6]),
            )
        duration = time.perf_counter() - start_mono
        # Roofline profiling: capture the pool program's cost analysis
        # once per pool config (the same identity note_compile tracks;
        # one extra lowering, no second backend compile) — AFTER the
        # duration stamp so the capture never inflates the first batch's
        # measured time. The batch is ONE device dispatch fenced by the
        # np.asarray above, so `duration` is the program's true wall time
        # (per BATCH — the view divides by executions).
        from tpu_render_cluster.obs.profiling import get_profiler, kernel_key

        profiler = get_profiler()
        pool_key = kernel_key(
            "raypool", scene_name,
            w=width, h=height, s=samples, b=max_bounces,
            pool=pool, frames=f_cap,
            tile="-" if region is None else f"{region[2]}x{region[3]}",
            **format_dims,
        )
        if not profiler.captured(pool_key):
            profiler.capture(
                pool_key, _raypool_batch, scene_name,
                jnp.asarray(padded, jnp.float32), jnp.int32(len(chunk)),
                jnp.int32(0 if region is None else region[0]),
                jnp.int32(0 if region is None else region[1]),
                width=width, height=height, samples=samples,
                max_bounces=max_bounces, pool_width=pool,
                tile_shape=None if region is None else (region[2], region[3]),
                use_tlas=tlas_resolved, tlas_leaf=tlas_leaf,
                tlas_block=tlas_block, quant=quant, builder=builder,
                wide=wide,
            )
        profiler.record_execute(pool_key, duration)
        _emit_batch_obs(
            scene_name=scene_name, n_chunk_frames=len(chunk), pool=pool,
            start_wall=start_wall, duration=duration,
            iterations=iterations, served=served, refilled=refilled,
            live_sum=live_sum, launched_sum=launched_sum,
            occ_log=occ_log, refill_log=refill_log,
        )
        images.extend(linear[:len(chunk)])
    return images


def render_frame_raypool(scene_name: str, frame_index, **kwargs):
    """Single-frame convenience wrapper; [H, W, 3] linear."""
    return render_batch_raypool(scene_name, [frame_index], **kwargs)[0]
