"""Path-tracing integrator: `lax.scan` over bounces, masked lanes.

TPU-first structure: no data-dependent control flow — every ray marches the
same fixed bounce count with an ``alive`` mask (dead lanes contribute
nothing); samples-per-pixel is a second ``lax.scan``; RNG is counter-based
(``jax.random.fold_in``) so any (frame, sample, pixel) is reproducible
without sequential state, which is what lets frames/tiles be rendered in any
order on any device.

Lighting: sun next-event-estimation (shadow ray per bounce) + emissive
spheres + sky on escape. Cosine-weighted hemisphere sampling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_render_cluster.render.camera import Camera, camera_rays, scene_camera
from tpu_render_cluster.render.geometry import (
    EPS,
    INF,
    checker_albedo,
    intersect_scene,
    occluded_sun,
    sky_color,
)
from tpu_render_cluster.render.scene import Scene, build_scene


def _cosine_sample_hemisphere(normals, key):
    """Cosine-weighted directions about unit normals [R, 3]."""
    u1, u2 = jax.random.uniform(key, (2,) + normals.shape[:1])
    r = jnp.sqrt(u1)
    phi = 2.0 * jnp.pi * u2
    x = r * jnp.cos(phi)
    y = r * jnp.sin(phi)
    z = jnp.sqrt(jnp.maximum(0.0, 1.0 - u1))
    # Build a tangent frame per normal.
    helper = jnp.where(
        jnp.abs(normals[:, 0:1]) > 0.9,
        jnp.array([0.0, 1.0, 0.0])[None, :],
        jnp.array([1.0, 0.0, 0.0])[None, :],
    )
    tangent = jnp.cross(helper, normals)
    tangent = tangent / jnp.linalg.norm(tangent, axis=-1, keepdims=True)
    bitangent = jnp.cross(normals, tangent)
    return (
        x[:, None] * tangent + y[:, None] * bitangent + z[:, None] * normals
    )


def _shade_bounce(scene: Scene, carry, key, mesh=None):
    """One bounce; returns the new path state and this bounce's radiance
    CONTRIBUTION (not accumulated — the caller owns accumulation, which
    under re-sorting travels with the lane and unsorts once at the
    end)."""
    origins, directions, throughput, alive = carry
    radiance = jnp.zeros_like(throughput)
    t, sphere_index, is_plane = intersect_scene(scene, origins, directions)
    mesh_closer = None
    if mesh is not None:
        from tpu_render_cluster.render.mesh import intersect_instances

        # Dead lanes contribute nothing but would still drive the packet
        # walks with stale rays; replace them with guaranteed-miss rays so
        # blocks of compacted dead lanes (see _ray_sort_order) cull every
        # instance at the top level.
        mesh_origins = jnp.where(alive[:, None], origins, 1e7)
        mesh_directions = jnp.where(
            alive[:, None],
            directions,
            jnp.array([0.0, 1.0, 0.0], jnp.float32)[None, :],
        )
        # Seeding with the sphere/plane t culls mesh-instance walks the
        # known hit already beats; a mesh miss returns t_mesh == t, which
        # the strict < below reads as "not closer".
        t_mesh, mesh_normals, mesh_albedo = intersect_instances(
            mesh.bvh, mesh.instances, mesh_origins, mesh_directions,
            init_t=jnp.where(alive, t, INF),
        )
        mesh_closer = alive & (t_mesh < t)
        t = jnp.minimum(t, t_mesh)
        is_plane = is_plane & ~mesh_closer
    hit = t < INF

    # Escaped rays pick up the sky and die.
    sky = sky_color(scene, directions)
    radiance = radiance + throughput * sky * (alive & ~hit)[:, None]

    alive = alive & hit
    points = origins + directions * t[:, None]
    sphere_normals = (points - scene.centers[sphere_index]) / jnp.maximum(
        scene.radii[sphere_index][:, None], 1e-6
    )
    plane_normal = jnp.array([0.0, 1.0, 0.0], jnp.float32)
    normals = jnp.where(is_plane[:, None], plane_normal[None, :], sphere_normals)

    albedo = jnp.where(
        is_plane[:, None],
        checker_albedo(scene, points),
        scene.albedo[sphere_index],
    )
    emission = jnp.where(
        is_plane[:, None],
        jnp.zeros((1, 3), jnp.float32),
        scene.emission[sphere_index],
    )
    if mesh_closer is not None:
        normals = jnp.where(mesh_closer[:, None], mesh_normals, normals)
        albedo = jnp.where(mesh_closer[:, None], mesh_albedo, albedo)
        emission = jnp.where(
            mesh_closer[:, None], jnp.zeros((1, 3), jnp.float32), emission
        )
    radiance = radiance + throughput * emission * alive[:, None]

    # Sun next-event estimation (delta light -> single shadow ray).
    cos_sun = jnp.maximum(normals @ scene.sun_direction, 0.0)
    shadow_origin = points + normals * EPS * 4.0
    sun_dir = jnp.broadcast_to(scene.sun_direction, normals.shape)
    in_shadow = occluded_sun(scene, shadow_origin, sun_dir)
    if mesh is not None:
        from tpu_render_cluster.render.mesh import occluded_instances

        # Lanes whose shadow result can't matter stop driving the mesh
        # walks (the result folds the mask back in): already shadowed by
        # the sphere any-hit, dead, or facing away from the sun (their
        # direct term is zero regardless — cos_sun clamps to 0). The
        # spurious True for masked lanes is harmless because every use of
        # in_shadow is multiplied by cos_sun * alive.
        in_shadow = occluded_instances(
            mesh.bvh, mesh.instances, shadow_origin, sun_dir,
            already=in_shadow | ~alive | (cos_sun <= 0.0),
        )
    direct = (
        albedo
        * scene.sun_color[None, :]
        * (cos_sun * (~in_shadow) * alive)[:, None]
        / jnp.pi
    )
    radiance = radiance + throughput * direct

    # Continue the path: cosine sample (BRDF/pi * cos / pdf == albedo).
    throughput = throughput * jnp.where(alive[:, None], albedo, 1.0)
    new_directions = _cosine_sample_hemisphere(normals, key)
    new_origins = points + normals * EPS * 4.0
    origins = jnp.where(alive[:, None], new_origins, origins)
    directions = jnp.where(alive[:, None], new_directions, directions)
    return (origins, directions, throughput, radiance, alive)


def _ray_sort_order(origins, directions, alive, mesh=None):
    """Coherence key: candidate instance, then Morton cell + octant.

    Deep-mesh scenes walk the instanced BVH kernels in [block] packets; a
    packet's cost is the UNION of its lanes' traversals and its top-level
    instance cull only fires when NO lane touches the instance. Diffuse
    bounce rays scatter lanes all over the scene, so packets degrade to
    worst-case. Sorting each bounce's rays by (candidate instance, origin
    cell, direction octant) re-packs blocks into packets that (a) mostly
    want the SAME instance first — its walk then seeds tight per-lane
    best-t that culls the rest — and (b) are spatially/directionally
    coherent. Lane order is semantically free (each lane is an
    independent path; the caller unsorts at the end).
    """
    candidate = jnp.zeros((origins.shape[0],), jnp.uint32)
    if mesh is not None:
        from tpu_render_cluster.render import pallas_kernels as pk

        # Shared broadphase (one fused [R, K] slab pass, ~1 ms at render
        # ray counts): the ray's nearest-entry overlapped instance AABB,
        # K (=instances) for rays overlapping nothing.
        table = pk.mesh_instance_table(mesh)
        candidate = pk.instance_entry_candidates(
            origins, directions, table[:, 13:16], table[:, 16:19]
        ).astype(jnp.uint32)
    # Quantize origin + one unit of travel: for scattered bounce origins
    # this is origin clustering with a directional nudge; for the shared-
    # origin primary bounce (where origin cells degenerate to one) it
    # becomes a spatial clustering of directions on the view sphere, far
    # finer than the 3-bit octant alone.
    point = origins + directions
    lo = jnp.min(point, axis=0)
    span = jnp.maximum(jnp.max(point, axis=0) - lo, 1e-6)
    cell = ((point - lo) / span * 31.999).astype(jnp.uint32)  # 5 bits/axis

    def part1by2(v):
        # Spread 5 bits to every 3rd position (classic Morton dilation).
        v = (v | (v << 8)) & jnp.uint32(0x0300F)
        v = (v | (v << 4)) & jnp.uint32(0x030C3)
        v = (v | (v << 2)) & jnp.uint32(0x09249)
        return v

    morton = (
        part1by2(cell[:, 0])
        | (part1by2(cell[:, 1]) << 1)
        | (part1by2(cell[:, 2]) << 2)
    )
    octant = (
        (directions[:, 0] > 0).astype(jnp.uint32)
        | ((directions[:, 1] > 0).astype(jnp.uint32) << 1)
        | ((directions[:, 2] > 0).astype(jnp.uint32) << 2)
    )
    # Dead lanes compact to the tail: together with the dead-lane ray
    # masking in _shade_bounce, blocks that are entirely dead cull every
    # instance at the top level and cost almost nothing.
    dead = (~alive).astype(jnp.uint32) << 31
    # Key layout: octant bits 0-2, Morton bits 3-17, candidate bits 18-30,
    # dead flag bit 31. Candidate is clamped to 13 bits so a scene with
    # 64+ instances can't spill into the dead flag (or wrap the uint32)
    # and silently destroy the compaction this sort exists for.
    candidate = jnp.minimum(candidate, jnp.uint32(0x1FFF))
    return jnp.argsort((candidate << 18) | (morton << 3) | octant | dead)


def tile_base_key(frame, y0, x0):
    """The (frame, y0, x0)-derived RNG root every tile render uses."""
    return jax.random.fold_in(
        jax.random.fold_in(
            jax.random.fold_in(
                jax.random.PRNGKey(917), jnp.asarray(frame).astype(jnp.int32)
            ),
            jnp.asarray(y0, jnp.int32),
        ),
        jnp.asarray(x0, jnp.int32),
    )


def tile_trace_key(base_key):
    """The path-trace key for a tile (sample index -1 = the trace
    stream, disjoint from every per-sample jitter stream)."""
    return jax.random.fold_in(base_key, jnp.int32(-1))


def trace_seed(key):
    """int32 scalar driving the Pallas kernels' in-kernel counter PCG."""
    return jax.random.key_data(key).ravel()[-1].astype(jnp.int32)


def sample_jitter_rays(
    camera: Camera, key, *, width, height, y0, x0, tile_height, tile_width
):
    """One sample's jittered primary rays for a tile."""
    jitter_key, _ = jax.random.split(key)
    jitter = jax.random.uniform(jitter_key, (tile_height * tile_width, 2))
    return camera_rays(
        camera, width, height, y0=y0, x0=x0,
        tile_height=tile_height, tile_width=tile_width, jitter=jitter,
    )


def flat_sample_rays(
    camera: Camera, base_key, *, width, height, y0, x0, tile_height,
    tile_width, samples,
):
    """All samples' rays flattened onto the ray axis ([S * n, 3] x 2).

    What render_tile traces for a tile; ``region_rays_and_seed`` derives
    the same rays for a region of the full frame.
    """
    n = tile_height * tile_width
    sample_keys = jax.vmap(lambda s: jax.random.fold_in(base_key, s))(
        jnp.arange(samples)
    )
    origins, directions = jax.vmap(
        lambda key: sample_jitter_rays(
            camera, key, width=width, height=height, y0=y0, x0=x0,
            tile_height=tile_height, tile_width=tile_width,
        )
    )(sample_keys)
    return origins.reshape(samples * n, 3), directions.reshape(samples * n, 3)


def region_pixel_indices(*, y0, x0, tile_height, tile_width, width):
    """Row-major FULL-frame pixel indices of one region ([th*tw] int32).

    ``y0``/``x0`` may be traced scalars."""
    return (
        (jnp.arange(tile_height, dtype=jnp.int32)[:, None]
         + jnp.asarray(y0, jnp.int32)) * width
        + jnp.arange(tile_width, dtype=jnp.int32)[None, :]
        + jnp.asarray(x0, jnp.int32)
    ).reshape(-1)


def region_lane_map(
    *, y0, x0, tile_height, tile_width, width, height, samples
):
    """Local region-ray index -> FULL-frame lane id ([samples*th*tw] int32).

    THE lane-layout definition (sample-major over row-major pixels:
    ``s*H*W + y*W + x``) the tiled-equals-untiled contract rests on.
    """
    pix = region_pixel_indices(
        y0=y0, x0=x0, tile_height=tile_height, tile_width=tile_width,
        width=width,
    )
    return (
        jnp.arange(samples, dtype=jnp.int32)[:, None] * (height * width)
        + pix[None, :]
    ).reshape(-1)


def region_rays_and_seed(
    camera: Camera, frame, *, width, height, samples, y0, x0,
    tile_height, tile_width,
):
    """One REGION's rows of the full frame's flattened primary rays, plus
    their GLOBAL lane ids and the frame's kernel trace seed.

    The cluster-tiling counterpart of ``flat_sample_rays``: instead of
    deriving a fresh RNG root from the tile coordinates (what
    ``render_tile(y0, x0)`` does — a different image per tiling), the
    region inherits the FULL frame's derivation. Per sample the whole
    frame's jitter array is drawn (cheap next to tracing) and sliced to
    the region's pixels, the camera rays are built from the same global
    pixel coordinates, and each ray carries its full-frame lane id
    ``s*H*W + y*W + x`` — the counter the Pallas kernels key their PCG
    streams on. Tracing these rays with these lane ids reproduces the
    whole-frame render's radiance at the region's pixels exactly, which
    is what makes a master-assembled tiled frame pixel-identical to the
    untiled render (tests/test_tiles.py pins it).

    ``y0``/``x0`` may be traced scalars (one compiled region program per
    tile SHAPE serves every tile position and frame).
    """
    base_key = tile_base_key(frame, 0, 0)
    n_frame = height * width
    pix = region_pixel_indices(
        y0=y0, x0=x0, tile_height=tile_height, tile_width=tile_width,
        width=width,
    )

    def one_sample(key):
        jitter_key, _ = jax.random.split(key)
        # The FULL frame's jitter, sliced: identical values to what
        # sample_jitter_rays feeds camera_rays for these pixels in the
        # whole-frame render.
        jitter = jax.random.uniform(jitter_key, (n_frame, 2))[pix]
        return camera_rays(
            camera, width, height, y0=y0, x0=x0,
            tile_height=tile_height, tile_width=tile_width, jitter=jitter,
        )

    sample_keys = jax.vmap(lambda s: jax.random.fold_in(base_key, s))(
        jnp.arange(samples)
    )
    origins, directions = jax.vmap(one_sample)(sample_keys)
    n_tile = tile_height * tile_width
    lanes = region_lane_map(
        y0=y0, x0=x0, tile_height=tile_height, tile_width=tile_width,
        width=width, height=height, samples=samples,
    )
    return (
        origins.reshape(samples * n_tile, 3),
        directions.reshape(samples * n_tile, 3),
        lanes,
        trace_seed(tile_trace_key(base_key)),
    )


# The deep per-bounce path's narrow launch widths: the ray set halved this
# many times. Each rung is one more instance of the bounce kernel in the
# frame's program (on a v5e host 1.5 s of tracing on every start and 2.7 s
# of compiling on a cold one, PERF.md §6 PR 29), so the ladder names only
# the steps a deep frame takes: a quarter for the bounce after which most
# rays are gone, an eighth and a sixteenth for the ones that follow.
_LADDER_HALVINGS = (2, 3, 4)


def launch_width_ladder(n: int) -> tuple[int, ...]:
    """The static widths a deep trace of ``n`` rays may run a bounce at,
    widest first: n, n/4, n/8, n/16, each rounded up to the bounce
    kernels' widest ray block (every block width divides it). A ray set
    of one block or less has the one rung ``n``."""
    from tpu_render_cluster.render.pallas_kernels import BVH_BLOCK_R

    widths = [n]
    for halving in _LADDER_HALVINGS:
        width = -(-max(n >> halving, 1) // BVH_BLOCK_R) * BVH_BLOCK_R
        if width < widths[-1]:
            widths.append(width)
    return tuple(widths)


def launch_rung(live, widths):
    """Index of the narrowest of ``widths`` (widest first) that holds
    ``live`` rays; ``live`` may be traced."""
    return jnp.sum(live <= jnp.asarray(widths[1:], jnp.int32), dtype=jnp.int32)


def _unsort(radiance, lane):
    """``radiance`` [n, 3] with row i moved to row ``lane[i]``; ``lane`` is
    a permutation of 0..n-1. Done as a sort on the lane with the three
    radiance columns riding it: that is the permutation's inverse exactly,
    with no arithmetic on radiance, and a sort streams its operands where
    the scatter it stands for (``zeros.at[lane].set(radiance)``) pays for
    every row (on a v5e, 2,097,152 rows: 3.6 ms against 84, PERF.md §6
    PR 31)."""
    with jax.named_scope("unsort"):
        # No two lanes are equal, so an unstable sort has the stable one's
        # result without the tie-breaking operand XLA adds for it (1.2 ms,
        # and 16 s of compiling on a cold start).
        _, *columns = jax.lax.sort(
            (lane, radiance[:, 0], radiance[:, 1], radiance[:, 2]),
            num_keys=1, is_stable=False,
        )
        return jnp.stack(columns, axis=1)


def _repack(order, arrays):
    """Each of ``arrays`` ([n] or [n, 3]) with row ``order[i]`` moved to
    row i — ``array[order]`` exactly — for ``order`` a permutation of
    0..n-1, with nothing gathered: a gather pays for every row it fetches
    where a sort streams its operands (on a v5e, a ray's twelve columns
    and its lane over 2,097,152 rows: 19.4 ms against 67.9, PERF.md §6
    PR 55). Row j goes to row ``rank[j]``, the inverse of ``order`` and
    itself a sort (``argsort``), so an [n, 3] array is ``_unsort`` by the
    rank and an [n] one a two-operand sort on it.

    One sort carrying everything would read the rank once and not once an
    array, but the TPU compiler's time for a sort grows faster than its
    operands (fourteen: 396 s; four: 28 s; two: 10 s, PERF.md §6 PR 55)
    while sorts of one shape are compiled once. So every sort here has a
    shape the program has already or one as small (``argsort``'s,
    ``_unsort``'s; the rank has no ties, so a payload's sort need not be
    stable), and the barrier keeps XLA from merging the sorts that share
    a key into the one of fourteen."""
    ranks = jax.lax.optimization_barrier((jnp.argsort(order),) * len(arrays))
    return [
        _unsort(array, rank) if array.ndim == 2
        else jax.lax.sort((rank, array), num_keys=1, is_stable=False)[1]
        for array, rank in zip(arrays, ranks)
    ]


# What a ray owns beside its lanes; the first three travel into a launch.
_RAY_COLUMNS = ("origins", "directions", "throughput", "radiance")


def _trace_paths_deep(
    scene, mesh, origins, directions, seed, *, max_bounces, rng_lanes,
    use_tlas, quant, live_counts, walk_counts=None,
):
    """trace_paths for deep-walk mesh scenes: the megakernel's
    bounce_step as ONE fused launch per bounce (sphere/plane/mesh
    nearest, NEE with both any-hits, shading, in-kernel PCG resample —
    pallas_kernels.mesh_bounce_pallas) with an XLA re-sort between
    bounces: rays re-pack by (candidate instance, Morton cell, octant)
    with dead lanes compacted to the tail, so the walks cull on tight
    coherent packets.

    The width of a bounce — of its re-pack, layout changes and launch,
    and of the sort that follows it — is the narrowest rung of
    ``launch_width_ladder`` that holds the bounce's live rays, picked
    inside the program by ``lax.switch`` on the live count (every rung a
    static shape, compiled once with the frame's program; no host sync).
    The sort key's dead flag puts every live ray before every dead one,
    so the first ``width`` entries of the sort order hold them all.

    A full-width bounce permutes everything a ray owns — the travelling
    state, the accumulated radiance, the unsort lane, the RNG lane where
    it is carried — by sorts on the order's inverse (``_repack``): random
    access costs per row and a sort streams, so n rows are cheapest moved
    as a sort's payload, then as one packed gather, and dearest as a
    gather a column (twelve columns of 2,097,152 rows on a v5e: 17 ms,
    53 ms, and about three times that; PERF.md §6 PR 55).
    Before the first bounce throughput and radiance are constants and
    stay where they are. A narrow bounce leaves radiance and the unsort
    lane where the last full-width permutation put them — a ray that
    died keeps its place, its radiance is final — and gathers only the
    travelling state of its rows ([width, 9]: few rows, a few
    milliseconds) with ``slot``, each row's place in that order, through
    which its contribution is added and its RNG lane read. Widths never
    grow again (rays only die), and a scene whose rays do not die takes
    the widest rung on every bounce. Per-ray
    arithmetic is the same at every width: same kernel, same RNG lane,
    the same four additions in the same order.

    After the last bounce the image's order is restored once, after the
    last ``switch``, by a sort on the carried lane (``_unsort``): a sort
    streams its operands, so it does not care that a ``conditional``'s
    results live in HBM, where a scatter pays for every row it places.
    """
    from tpu_render_cluster.render import pallas_kernels

    n = origins.shape[0]
    if max_bounces < 1:
        return jnp.zeros((n, 3), jnp.float32)
    tlas = pallas_kernels.use_tlas_for(
        mesh.instances.translation.shape[0], use_tlas
    )
    quant = pallas_kernels.bvh_quant_mode() if quant is None else int(quant)
    widths = launch_width_ladder(n)

    def sort_order(origins, directions, alive, keys):
        if tlas:
            return jnp.argsort(keys)
        return _ray_sort_order(origins, directions, alive, mesh=mesh)

    # One traced kernel per width, whatever the bounce (its index is a
    # scalar operand): the bounces that may run at a width share it. Made
    # here, so it lives and dies with this trace.
    launch = jax.jit(
        pallas_kernels.mesh_bounce_pallas,
        static_argnames=("total_bounces", "use_tlas", "quant"),
    )

    def bounce_at(width, bounce, state):
        full = width == n
        with jax.named_scope("resort"):
            order = state["order"][:width]
            if full:
                # Before the first bounce throughput and radiance are
                # constants: a permutation of them is them. The RNG
                # counter rides separately from the unsort index when the
                # caller supplies full-frame lane ids (region rendering);
                # with positional lanes the two are one array.
                columns = _RAY_COLUMNS[:2] if bounce == 0 else _RAY_COLUMNS
                names = [k for k in ("lane", "rng", *columns) if k in state]
                moved = _repack(order, [state[k] for k in names])
                ray = {**state, **dict(zip(names, moved))}
                lane = ray["lane"]
                rng = ray.get("rng", lane)
                travelling = [ray[k] for k in _RAY_COLUMNS[:3]]
            else:
                packed = jnp.concatenate(
                    [state[k] for k in _RAY_COLUMNS[:3]], axis=1
                )[order]
                travelling = [packed[:, 0:3], packed[:, 3:6], packed[:, 6:9]]
                lane = state["lane"]
                slot = state["slot"][order]
                rng = state.get("rng", lane)[slot]
            # Lanes >= live are exactly the dead tail: the kernel's
            # live-count prefetch skips those blocks outright
            # (behavior-preserving — dead lanes pass through a masked
            # bounce unchanged anyway). The carried ORIGINAL lane id is
            # the RNG counter, so a ray's stream survives every
            # permutation.
            alive = jnp.arange(width, dtype=jnp.int32) < state["live"]
        with jax.named_scope("bounce"):
            # a streamed BLAS's launch also returns its walk's counts
            contribution, origins, directions, throughput, alive, keys, *walk = launch(
                scene, mesh, *travelling, alive, seed, jnp.int32(bounce),
                total_bounces=max_bounces,
                lane=rng, live_count=state["live"], use_tlas=tlas,
                quant=quant,
            )
        with jax.named_scope("accumulate"):
            if full:
                radiance = ray["radiance"] + contribution
            else:
                radiance = state["radiance"].at[slot].add(
                    contribution, unique_indices=True
                )
        counted = {"walk": state["walk"].at[bounce].set(walk[0])} if walk else {}
        if bounce + 1 == max_bounces:
            return (radiance, lane, *counted.values())
        new = dict(state, radiance=radiance, lane=lane, **counted)
        with jax.named_scope("resort"):
            # The next bounce's sort, over this bounce's width: its live
            # rays are among these rows and nowhere else.
            order = sort_order(origins, directions, alive, keys)
            new["live"] = jnp.sum(alive, dtype=jnp.int32)
            if full:
                new.update(
                    origins=origins, directions=directions,
                    throughput=throughput, order=order,
                )
                if "rng" in state:
                    new["rng"] = rng
            else:
                for name, rows in (
                    ("origins", origins), ("directions", directions),
                    ("throughput", throughput), ("slot", slot),
                    ("order", order),
                ):
                    new[name] = state[name].at[:width].set(rows)
        return new

    lane = jnp.arange(n, dtype=jnp.int32)
    alive = jnp.ones((n,), bool)
    keys = None
    with jax.named_scope("resort"):
        if tlas:
            # Bounce 0 has no kernel-emitted key column yet: derive the
            # initial keys through the XLA twin of the kernels' fused
            # epilogue (bit-identical derivation, pinned by
            # tests/test_tlas.py). Later bounces read the key column the
            # bounce kernel wrote while the state was still VMEM-resident.
            keys = pallas_kernels.initial_mesh_sort_keys(
                mesh, origins, directions, alive
            )
        order = sort_order(origins, directions, alive, keys)
    state = dict(
        origins=origins, directions=directions,
        throughput=jnp.ones((n, 3), jnp.float32),
        radiance=jnp.zeros((n, 3), jnp.float32),
        lane=lane, slot=lane, order=order, live=jnp.int32(n),
    )
    if rng_lanes is not None:
        state["rng"] = jnp.asarray(rng_lanes, jnp.int32)
    if mesh.bvh.stream is not None:
        # each bounce launch's counts
        state["walk"] = jnp.zeros(
            (max_bounces, len(pallas_kernels.WALK_COUNTS)), jnp.int32
        )
    for bounce in range(max_bounces):
        # Every ray is live at the first bounce, and a one-rung ladder
        # leaves nothing to pick: no switch in the program.
        pick = bounce > 0 and len(widths) > 1
        rung = launch_rung(state["live"], widths) if pick else jnp.int32(0)
        if live_counts is not None:
            live_counts.append(
                jnp.stack([state["live"], jnp.asarray(widths, jnp.int32)[rung]])
            )
        if pick:
            state = jax.lax.switch(
                rung,
                [functools.partial(bounce_at, w, bounce) for w in widths],
                state,
            )
        else:
            state = bounce_at(n, bounce, state)
    radiance, lane, *walk = state  # the last bounce's
    if walk and walk_counts is not None:
        walk_counts.extend(walk[0])
    return _unsort(radiance, lane)


# What traces a frame's rays, by the name the backend counts its frames
# under (``render_trace_kernel_frames_total{kernel}``): the sphere
# megakernel, the mesh megakernel, one ``mesh_bounce_pallas`` launch a
# bounce over a resident BLAS or over one streamed from HBM, and, where the
# Pallas kernels are off, the XLA bounce loop.
TRACE_KERNELS = (
    "sphere_fused", "mesh_fused", "mesh_bounce", "mesh_stream", "xla_loop",
)


def trace_kernel_name(mesh, rng_lanes=None) -> str:
    """Which of ``TRACE_KERNELS`` traces rays over ``mesh`` (None: spheres
    alone) with or without explicit lane ids. ``trace_paths`` dispatches by
    this name and by nothing else, so what a counter or a span says of a
    frame's program is the road its rays took.

    A mesh goes to the megakernel (the whole bounce loop with the
    instanced BVH walk in one kernel) where it is resident and shallow
    (nodes x instances <= MESH_MEGAKERNEL_MAX_WALK, the kernel's
    precondition) and no lane ids are given; everything deeper, streamed
    or with explicit lanes takes one bounce kernel a bounce. The
    megakernel's in-walk normal / albedo tracking adds work to EVERY leaf
    visit; where the gate should lie against today's bounce kernel is
    ROADMAP D19's."""
    from tpu_render_cluster.render import pallas_kernels

    if not pallas_kernels.pallas_enabled():
        return "xla_loop"
    if mesh is None:
        return "sphere_fused"
    if mesh.bvh.stream is not None:
        return "mesh_stream"
    if rng_lanes is None and pallas_kernels.mesh_megakernel_eligible(mesh):
        return "mesh_fused"
    return "mesh_bounce"


def scene_trace_kernel(scene_name: str, *, region: bool = False) -> str:
    """``trace_kernel_name`` of a scene family's programs: the whole
    frame's, or a region's (which hands its rays' full-frame lane ids
    over). Outside any trace and without a device operation: the name
    function reads shapes alone, so the family's mesh set is asked for as
    shapes (the BLAS is the cached one the renderer factories close over;
    the instances' count is the same on every frame)."""
    from tpu_render_cluster.render.mesh import scene_blas_stream, scene_mesh_set

    _tlas, _quant, builder, wide = resolve_bvh_config()
    blas = scene_blas_stream(scene_name, builder, wide)
    mesh = jax.eval_shape(
        lambda frame: scene_mesh_set(scene_name, frame, builder, wide, stream=blas),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    return trace_kernel_name(mesh, rng_lanes=True if region else None)


def trace_paths(
    scene: Scene, origins, directions, key, *, max_bounces: int = 4, mesh=None,
    rng_lanes=None, use_tlas=None, quant=None, live_counts=None,
    walk_counts=None,
) -> jnp.ndarray:
    """Trace one sample per ray; returns radiance [R, 3].

    Where ``pallas_enabled()`` (on the chip, or ``TRC_PALLAS=1``) one of
    three Pallas kernels traces the rays, chosen by ``trace_kernel_name``:
    the sphere megakernel (``trace_paths_fused``: the whole bounce loop in
    one kernel, path state VMEM-resident, counter-based in-kernel RNG), the
    mesh megakernel for a shallow resident mesh
    (``trace_paths_fused_mesh``, ``mesh_megakernel_eligible``), or one
    ``mesh_bounce_pallas`` launch a bounce with the rays re-sorted between
    (``_trace_paths_deep``). Elsewhere the XLA bounce loop below runs: the
    reference every kernel is tested against. The two sides use different
    RNG streams but identical physics, so images agree statistically, not
    bit-for-bit.

    ``rng_lanes`` (optional [R] int32) overrides the RNG counter per ray:
    the region render path (cluster tiling) passes each ray's FULL-frame
    lane id so a cropped trace reproduces the whole-frame streams. Only
    meaningful on the Pallas paths — with it set, a sphere scene's
    megakernel reads its RNG counters from the lane row, every mesh
    scene routes through the per-bounce kernel (which accepts explicit
    lane ids; per-lane streams match the megakernels', pinned by
    tests/test_tiles.py), and the XLA fallback ignores it (shape-derived
    RNG cannot be cropped — region renders there are statistically, not
    bitwise, consistent).

    ``use_tlas`` (None = the ``TRC_TLAS`` env tier, default on) selects
    the two-level TLAS kernel variants for mesh scenes. Per-lane results
    are identical either way (instance visit order is semantically free;
    only packet-cull efficiency changes); on the deep per-bounce path the
    TLAS kernels additionally emit the next bounce's coherence sort key
    from their epilogue, so the re-sort below reads one precomputed
    column instead of re-deriving keys from the full ray state.

    ``live_counts`` (optional list) collects, on the deep per-bounce path
    only, each bounce launch's (live rays, width) — a traced int32 [2]:
    the count the launch already computes for its tail skip and the rung
    of ``launch_width_ladder`` the program ran the bounce at — so the
    caller can return them from the same program: the frame's launch
    occupancy at no extra sync. Other paths launch no per-bounce
    kernel and leave the list empty. ``walk_counts`` (optional list)
    collects the same launches' walk counts
    (``pallas_kernels.WALK_COUNTS``) where the mesh's BLAS is streamed
    from HBM (``mesh.bvh.stream``).
    """
    from tpu_render_cluster.render import pallas_kernels

    kernel = trace_kernel_name(mesh, rng_lanes)
    if kernel == "sphere_fused":
        # With explicit lane ids: the SAME fused megakernel, with the RNG
        # counters read from the caller's lane row instead of the launch
        # position — a cropped region launch therefore runs
        # bitwise-identical per-lane math to the whole-frame render.
        return pallas_kernels.trace_paths_fused(
            scene, origins, directions, trace_seed(key),
            max_bounces=max_bounces,
            lane=None if rng_lanes is None else jnp.asarray(rng_lanes, jnp.int32),
        )
    if kernel == "mesh_fused":
        return pallas_kernels.trace_paths_fused_mesh(
            scene, mesh, origins, directions, trace_seed(key),
            max_bounces=max_bounces, use_tlas=use_tlas, quant=quant,
        )
    if kernel in ("mesh_bounce", "mesh_stream"):
        return _trace_paths_deep(
            scene, mesh, origins, directions, trace_seed(key),
            max_bounces=max_bounces, rng_lanes=rng_lanes, use_tlas=use_tlas,
            quant=quant, live_counts=live_counts, walk_counts=walk_counts,
        )
    if mesh is not None and mesh.bvh.stream is not None:
        raise NotImplementedError(
            "a BLAS streamed from HBM is walked by the Pallas bounce kernel "
            "alone: set TRC_PALLAS=1 to render this scene off the chip"
        )
    # Non-Pallas reference path: the plain XLA bounce loop. Order-invariant
    # per lane, so no sort machinery.
    n = origins.shape[0]
    throughput = jnp.ones((n, 3), jnp.float32)
    radiance = jnp.zeros((n, 3), jnp.float32)
    alive = jnp.ones((n,), bool)
    keys = jax.random.split(key, max_bounces)

    for bounce in range(max_bounces):
        origins, directions, throughput, contribution, alive = _shade_bounce(
            scene,
            (origins, directions, throughput, alive),
            keys[bounce],
            mesh=mesh,
        )
        radiance = radiance + contribution
    return radiance


@functools.partial(
    jax.jit,
    static_argnames=(
        "width", "height", "tile_height", "tile_width", "samples",
        "max_bounces", "use_tlas", "quant", "with_live", "with_walk",
    ),
)
def render_tile(
    scene: Scene,
    camera: Camera,
    frame: jnp.ndarray,
    y0,
    x0,
    *,
    width: int,
    height: int,
    tile_height: int,
    tile_width: int,
    samples: int = 8,
    max_bounces: int = 4,
    mesh=None,
    use_tlas=None,
    quant=None,
    with_live: bool = False,
    with_walk: bool = False,
) -> jnp.ndarray:
    """Render a tile; returns [tile_height, tile_width, 3] linear radiance.

    The RNG key derives from (frame, y0, x0, sample) so any tile of any
    frame renders identically regardless of device/order. ``use_tlas``
    (static; None = env tier) selects the two-level mesh kernel variant
    — a distinct value is a distinct compiled program, which is what
    lets the interleaved A/B bench run both variants in one process.

    ``with_live`` (static) returns ``(radiance, live)`` instead, ``live``
    the int32 [max_bounces, 2] (live rays, width) of each per-bounce
    launch (trace_paths' ``live_counts``), or None where the scene's
    path launches no per-bounce kernel. ``with_walk`` (static, a scene
    whose BLAS is streamed) appends ``walk``, the int32
    [max_bounces, len(pallas_kernels.WALK_COUNTS)] counts of the same
    launches' walks.
    """
    n = tile_height * tile_width
    base_key = tile_base_key(frame, y0, x0)
    live_counts = [] if with_live else None
    walk_counts = [] if with_walk else None

    from tpu_render_cluster.render import pallas_kernels

    # Samples always ride the ray axis under Pallas. Deep-walk mesh scenes
    # used to keep a sequential per-sample scan (flattening interleaved
    # jitter streams and widened the packets the BVH walk culls on —
    # measured 1.89 -> 1.64 f/s on 03_physics-2-mesh before re-sorting);
    # the per-bounce Morton re-sort in trace_paths now re-packs the
    # flattened rays into coherent blocks regardless of sample
    # interleaving, so flattening is a pure win (4x fewer kernel launches
    # for the same total work).
    flatten_samples = pallas_kernels.pallas_enabled()
    if flatten_samples:
        # Samples ride the ray axis instead of a sequential lax.scan: one
        # [samples * n]-ray trace keeps every bounce step 'samples'x larger
        # (better VPU/MXU occupancy, fewer serialized steps) for the same
        # total work — a measured ~1.9x on a single chip. Safe here because
        # the fused kernel blocks rays at BLOCK_R; its VMEM working set is
        # independent of the flattened ray count.
        with jax.named_scope("raygen"):
            origins, directions = flat_sample_rays(
                camera, base_key, width=width, height=height, y0=y0, x0=x0,
                tile_height=tile_height, tile_width=tile_width,
                samples=samples,
            )
        radiance = trace_paths(
            scene,
            origins,
            directions,
            tile_trace_key(base_key),
            max_bounces=max_bounces,
            mesh=mesh,
            use_tlas=use_tlas,
            quant=quant,
            live_counts=live_counts,
            walk_counts=walk_counts,
        )
        image = radiance.reshape(samples, n, 3).mean(axis=0)
    else:
        # The XLA fallback materializes [R, N] intersection intermediates,
        # so the flattened [samples * n] ray axis would multiply peak memory
        # by 'samples' (an OOM risk for big tiles on CPU/GPU workers); keep
        # the sequential per-sample scan there instead.
        sample_keys = jax.vmap(lambda s: jax.random.fold_in(base_key, s))(
            jnp.arange(samples)
        )

        def sample_step(acc, key):
            origins, directions = sample_jitter_rays(
                camera, key, width=width, height=height, y0=y0, x0=x0,
                tile_height=tile_height, tile_width=tile_width,
            )
            _, trace_key = jax.random.split(key)
            radiance = trace_paths(
                scene, origins, directions, trace_key,
                max_bounces=max_bounces, mesh=mesh,
            )
            return acc + radiance, None

        total, _ = jax.lax.scan(
            sample_step, jnp.zeros((n, 3), jnp.float32), sample_keys
        )
        image = total / samples
    image = image.reshape(tile_height, tile_width, 3)
    if with_live or with_walk:
        live = jnp.stack(live_counts) if live_counts else None
        if with_walk:
            return image, live, jnp.stack(walk_counts)
        return image, live
    return image


def render_frame(
    scene_name: str,
    frame_index: int,
    *,
    width: int = 512,
    height: int = 512,
    samples: int = 8,
    max_bounces: int = 4,
    tile_size: int | None = None,
) -> jnp.ndarray:
    """Render a full frame on the default device; returns [H, W, 3] linear."""
    from tpu_render_cluster.render.mesh import scene_blas_stream, scene_mesh_set

    scene = build_scene(scene_name, frame_index)
    camera = scene_camera(scene_name, frame_index)
    # BVH env tiers resolve HERE, outside the jitted tile renders.
    _tlas, bvh_quant, bvh_builder, bvh_wide = resolve_bvh_config()
    mesh = scene_mesh_set(
        scene_name, frame_index, bvh_builder, bvh_wide,
        stream=scene_blas_stream(scene_name, bvh_builder, bvh_wide),
    )
    frame = jnp.asarray(frame_index, jnp.float32)
    if tile_size is None:
        return render_tile(
            scene,
            camera,
            frame,
            0,
            0,
            width=width,
            height=height,
            tile_height=height,
            tile_width=width,
            samples=samples,
            max_bounces=max_bounces,
            mesh=mesh,
            quant=bvh_quant,
        )
    rows = []
    for y0 in range(0, height, tile_size):
        row = []
        for x0 in range(0, width, tile_size):
            row.append(
                render_tile(
                    scene,
                    camera,
                    frame,
                    y0,
                    x0,
                    width=width,
                    height=height,
                    tile_height=min(tile_size, height - y0),
                    tile_width=min(tile_size, width - x0),
                    samples=samples,
                    max_bounces=max_bounces,
                    mesh=mesh,
                    quant=bvh_quant,
                )
            )
        rows.append(jnp.concatenate(row, axis=1))
    return jnp.concatenate(rows, axis=0)


def tonemap(image: jnp.ndarray) -> jnp.ndarray:
    """Linear -> display: Reinhard + gamma 2.2, uint8."""
    mapped = image / (1.0 + image)
    srgb = jnp.power(jnp.clip(mapped, 0.0, 1.0), 1.0 / 2.2)
    return (srgb * 255.0 + 0.5).astype(jnp.uint8)


def resolve_bvh_config(use_tlas=None, quant=None, builder=None, wide=None):
    """Resolve the BVH env tiers (``TRC_TLAS``/``TRC_BVH_QUANT``/
    ``TRC_BVH_BUILDER``/``TRC_BVH_WIDE``) to concrete values — the ONE
    site the jitted renderer factories resolve them through, OUTSIDE any
    trace (the ``env-tiers`` lint contract), so a mid-process env toggle
    resolves to a fresh cache key instead of a stale compiled program or
    tree."""
    from tpu_render_cluster.render.mesh import bvh_builder, bvh_wide
    from tpu_render_cluster.render.pallas_kernels import (
        bvh_quant_mode,
        tlas_enabled,
    )

    return (
        tlas_enabled() if use_tlas is None else bool(use_tlas),
        bvh_quant_mode() if quant is None else max(0, min(int(quant), 2)),
        bvh_builder() if builder is None else str(builder),
        bvh_wide() if wide is None else max(1, min(int(wide), 8)),
    )


class _WithBlasTables:
    """A jitted program whose last argument is its scene's streamed BLAS
    (``mesh.scene_blas_stream``: HBM tables, or None for every other
    scene), with that argument bound: called, traced and lowered as the
    program of the arguments that are left."""

    def __init__(self, program, blas):
        self.program, self.blas = program, blas

    def __call__(self, *args):
        return self.program(*args, self.blas)

    def trace(self, *args):
        return self.program.trace(*args, self.blas)

    def lower(self, *args):
        return self.program.lower(*args, self.blas)


@functools.lru_cache(maxsize=32)
def _fused_frame_renderer(
    scene_name: str,
    width: int,
    height: int,
    samples: int,
    max_bounces: int,
    use_tlas: bool,
    quant: int,
    builder: str,
    wide: int,
    with_live: bool = False,
):
    from tpu_render_cluster.obs import render_compile_counter
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.scene import build_scene

    # This body runs on the lru_cache's miss only: one program built.
    render_compile_counter().inc()
    from tpu_render_cluster.render.mesh import scene_blas_stream

    # A streamed BLAS is an argument of the program, built (once a
    # process) here and not inside the trace; None for every other scene,
    # whose program is the one it always was.
    blas = scene_blas_stream(scene_name, builder, wide)
    with_walk = with_live and blas is not None

    @jax.jit
    def program(frame: jnp.ndarray, blas) -> jnp.ndarray:
        from tpu_render_cluster.render.mesh import scene_mesh_set

        scene = build_scene(scene_name, frame)
        camera = scene_camera(scene_name, frame)
        mesh = scene_mesh_set(scene_name, frame, builder, wide, stream=blas)
        rendered = render_tile(
            scene,
            camera,
            jnp.asarray(frame, jnp.float32),
            0,
            0,
            width=width,
            height=height,
            tile_height=height,
            tile_width=width,
            samples=samples,
            max_bounces=max_bounces,
            mesh=mesh,
            use_tlas=use_tlas,
            quant=quant,
            with_live=with_live,
            with_walk=with_walk,
        )
        if with_walk:
            linear, live, walk = rendered
            return tonemap(linear), live, walk
        if with_live:
            linear, live = rendered
            return tonemap(linear), live
        return tonemap(rendered)

    return _WithBlasTables(program, blas)


def fused_frame_renderer(
    scene_name: str,
    width: int,
    height: int,
    samples: int,
    max_bounces: int,
    use_tlas: bool | None = None,
    quant: int | None = None,
    builder: str | None = None,
    wide: int | None = None,
    with_live: bool = False,
):
    """A jitted ``frame -> uint8 [H, W, 3]`` closure for one scene/config.

    Fuses scene build + camera + path trace + tonemap into a single XLA
    program, so rendering a frame is ONE device dispatch. The eager
    alternative (build_scene / scene_camera outside jit, as render_frame
    does) pays a device round-trip per tiny scene array — tens of
    dispatches per frame.

    ``use_tlas``/``quant``/``builder``/``wide`` (None = env tiers,
    resolved HERE — outside the trace) are part of the cache key AND the
    compiled program's identity: one process can hold one renderer per
    node-format variant, and an env toggle between calls gets a fresh
    renderer with a matching tree instead of a stale cache hit.

    ``with_live`` makes the closure return ``(image, live)`` from the
    same program: ``live`` is render_tile's per-bounce (live rays,
    launch width) (int32 [max_bounces, 2]) for a deep mesh scene and None
    for every other scene, whose program is then the one without it.
    """
    return _fused_frame_renderer(
        scene_name, width, height, samples, max_bounces,
        *resolve_bvh_config(use_tlas, quant, builder, wide),
        with_live,
    )


fused_frame_renderer.cache_clear = _fused_frame_renderer.cache_clear


@functools.lru_cache(maxsize=64)
def _fused_region_renderer(
    scene_name: str,
    width: int,
    height: int,
    tile_height: int,
    tile_width: int,
    samples: int,
    max_bounces: int,
    use_tlas: bool,
    quant: int,
    builder: str,
    wide: int,
):
    from tpu_render_cluster.obs import render_compile_counter
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.scene import build_scene

    render_compile_counter().inc()
    from tpu_render_cluster.render.mesh import scene_blas_stream

    blas = scene_blas_stream(scene_name, builder, wide)  # see the frame renderer

    @jax.jit
    def program(frame: jnp.ndarray, y0, x0, blas) -> jnp.ndarray:
        from tpu_render_cluster.render.mesh import scene_mesh_set

        scene = build_scene(scene_name, frame)
        camera = scene_camera(scene_name, frame)
        mesh = scene_mesh_set(scene_name, frame, builder, wide, stream=blas)
        with jax.named_scope("raygen"):
            origins, directions, lanes, seed = region_rays_and_seed(
                camera, jnp.asarray(frame, jnp.float32),
                width=width, height=height, samples=samples,
                y0=y0, x0=x0, tile_height=tile_height, tile_width=tile_width,
            )
        base_key = tile_base_key(jnp.asarray(frame, jnp.float32), 0, 0)
        n = tile_height * tile_width
        from tpu_render_cluster.render import pallas_kernels

        if pallas_kernels.pallas_enabled():
            radiance = trace_paths(
                scene, origins, directions, tile_trace_key(base_key),
                max_bounces=max_bounces, mesh=mesh, rng_lanes=lanes,
                use_tlas=use_tlas, quant=quant,
            )
        else:
            # XLA fallback: per-lane counters don't exist there, so the
            # region renders with its own shape-derived streams —
            # statistically the same image, not bitwise (the Pallas tiers
            # carry the exactness contract).
            radiance = trace_paths(
                scene, origins, directions, tile_trace_key(base_key),
                max_bounces=max_bounces, mesh=mesh,
            )
        return radiance.reshape(samples, n, 3).mean(axis=0).reshape(
            tile_height, tile_width, 3
        )

    return _WithBlasTables(program, blas)


def fused_region_renderer(
    scene_name: str,
    width: int,
    height: int,
    tile_height: int,
    tile_width: int,
    samples: int,
    max_bounces: int,
    use_tlas: bool | None = None,
    quant: int | None = None,
    builder: str | None = None,
    wide: int | None = None,
):
    """A jitted ``(frame, y0, x0) -> [th, tw, 3] LINEAR`` region closure.

    The cluster-tile path: one compiled program
    per tile SHAPE (``y0``/``x0`` are traced), so every tile of a grid —
    and every frame — reuses the same executable. The region traces the
    full frame's rays-and-RNG restricted to its pixels
    (``region_rays_and_seed``), so stitching a grid of regions is
    pixel-identical to the whole-frame render (up to the FP ties of the
    megakernel-vs-state-io kernel pairing; see ``trace_paths``).

    Returns LINEAR radiance (not tonemapped): callers tonemap after
    (matching render_frame's contract) so the assembly seam test can
    compare linear images. BVH node-format knobs resolve like
    ``fused_frame_renderer``'s — outside the trace, into the cache key.
    """
    return _fused_region_renderer(
        scene_name, width, height, tile_height, tile_width, samples,
        max_bounces, *resolve_bvh_config(use_tlas, quant, builder, wide),
    )


fused_region_renderer.cache_clear = _fused_region_renderer.cache_clear


def render_frame_region(
    scene_name: str,
    frame_index: int,
    *,
    y0: int,
    x0: int,
    tile_height: int,
    tile_width: int,
    width: int = 512,
    height: int = 512,
    samples: int = 8,
    max_bounces: int = 4,
) -> jnp.ndarray:
    """Render one region of a frame; [tile_height, tile_width, 3] linear.

    Equals the whole-frame render's pixels on the region (the cluster
    tiling contract) — see ``fused_region_renderer``.
    """
    return fused_region_renderer(
        scene_name, width, height, tile_height, tile_width, samples,
        max_bounces,
    )(jnp.asarray(frame_index, jnp.float32), y0, x0)
