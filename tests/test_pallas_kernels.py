"""The sphere megakernel vs the XLA bounce loop, the reference.

Runs in interpret mode on the CPU test mesh (tests/conftest.py pins
JAX_PLATFORMS=cpu), exercising the identical kernel code that compiles on
TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_render_cluster.render import pallas_kernels
from tpu_render_cluster.render.camera import camera_rays, scene_camera
from tpu_render_cluster.render.pallas_kernels import EPS, INF, trace_paths_fused
from tpu_render_cluster.render.scene import SCENE_NAMES, build_scene


def _random_rays(n, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    origins = jax.random.normal(k1, (n, 3)) * 4.0 + jnp.array([0.0, 3.0, 8.0])
    directions = jax.random.normal(k2, (n, 3))
    directions = directions / jnp.linalg.norm(directions, axis=-1, keepdims=True)
    return origins.astype(jnp.float32), directions.astype(jnp.float32)


def _one_bounce_matches_the_xla_loop(monkeypatch, scene, origins, directions):
    """One bounce of the megakernel against one bounce of the XLA loop on
    the same rays. At one bounce the radiance is sky, emission and the sun
    test of the first hit: the resampled direction is never traced, so the
    two RNG streams do not enter and every lane must agree."""
    from tpu_render_cluster.render.integrator import trace_paths

    monkeypatch.setenv("TRC_PALLAS", "0")  # read at trace time
    jax.clear_caches()
    want = np.asarray(
        trace_paths(
            scene, origins, directions, jax.random.PRNGKey(5), max_bounces=1
        )
    )
    jax.clear_caches()
    got = np.asarray(trace_paths_fused(scene, origins, directions, 5, max_bounces=1))
    assert got.shape == want.shape == (origins.shape[0], 3)
    assert want.max() > 0.1  # light reached something
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("scene_name", SCENE_NAMES)
def test_megakernel_matches_xla_loop_random_rays(monkeypatch, scene_name):
    """Every family's sphere table, rays from anywhere (under the plane
    and inside spheres too), a count that is no multiple of BLOCK_R."""
    origins, directions = _random_rays(513, seed=3)
    _one_bounce_matches_the_xla_loop(
        monkeypatch, build_scene(scene_name, 7), origins, directions
    )


def test_megakernel_matches_xla_loop_camera_rays(monkeypatch):
    camera = scene_camera("04_very-simple", 1)
    origins, directions = camera_rays(
        camera, 32, 32, y0=0, x0=0, tile_height=32, tile_width=32,
        jitter=jnp.zeros((32 * 32, 2)),
    )
    _one_bounce_matches_the_xla_loop(
        monkeypatch, build_scene("04_very-simple", 1), origins, directions
    )


def _render_both_paths(monkeypatch, **kwargs):
    """Render the same frame via the XLA path and the fused Pallas path.

    The two paths share primary-ray generation (same jitter stream) but use
    different bounce-RNG streams (fold_in/split vs in-kernel counter PCG),
    so only RNG-free components match exactly — see the two tests below.
    """
    from tpu_render_cluster.render.integrator import render_frame

    monkeypatch.setenv("TRC_PALLAS", "0")
    jax.clear_caches()  # env is read at trace time
    ref = np.asarray(render_frame("04_very-simple", 1, **kwargs))
    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    out = np.asarray(render_frame("04_very-simple", 1, **kwargs))
    jax.clear_caches()
    return out, ref


def test_deterministic_render_matches_reference_path(monkeypatch):
    """Single-bounce renders must agree bit-for-bit-ish across paths.

    With max_bounces=1 the radiance is sky + emission + sun NEE of the
    primary hit only — the bounce RNG samples directions that are never
    traced — so the fused kernel and the XLA scan compute the same
    function and any mismatch is a physics bug, not noise.
    """
    out, ref = _render_both_paths(
        monkeypatch, width=32, height=32, samples=2, max_bounces=1
    )
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


def test_sun_disc_escape_matches_reference_path(monkeypatch):
    """Escape radiance toward the sun (sky + sun disc) is RNG-free.

    The sun-disc term covers too small a solid angle for the statistical
    test to notice, so compare it deterministically: rays that escape at
    bounce 0 take sky_color() in the XLA path and the in-kernel sky+disc
    in the fused path, with the RNG never consulted.
    """
    from tpu_render_cluster.render.integrator import trace_paths

    # Pin the reference to the XLA path: trace_paths dispatches to the
    # fused kernel when pallas is enabled (e.g. on a real TPU backend).
    monkeypatch.setenv("TRC_PALLAS", "0")
    jax.clear_caches()

    scene = build_scene("04_very-simple", 1)
    n = 128
    origins = jnp.tile(jnp.array([[0.0, 50.0, 0.0]], jnp.float32), (n, 1))
    # Half the rays stare into the sun disc, half just outside it.
    sun = np.asarray(scene.sun_direction)
    off = sun + np.array([0.05, 0.0, 0.0])
    off = off / np.linalg.norm(off)
    directions = jnp.asarray(
        np.where(np.arange(n)[:, None] % 2 == 0, sun[None, :], off[None, :]),
        jnp.float32,
    )
    ref = trace_paths(
        scene, origins, directions, jax.random.PRNGKey(5), max_bounces=1
    )
    out = trace_paths_fused(scene, origins, directions, 5, max_bounces=1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-3
    )


def test_stochastic_render_agrees_statistically(monkeypatch):
    """High-spp renders from the two RNG streams must converge together.

    At 256 spp the Monte-Carlo error of each estimate is small enough that
    a genuine physics divergence (e.g. a broken sky or indirect-bounce
    term) shifts the image mean and per-pixel values well outside these
    bounds, while pure RNG-stream differences stay inside them.
    """
    out, ref = _render_both_paths(
        monkeypatch, width=16, height=16, samples=256, max_bounces=3
    )
    # Image-wide mean: MC noise averages out over 16*16*256 samples.
    np.testing.assert_allclose(out.mean(), ref.mean(), rtol=0.01)
    # Per-channel means.
    np.testing.assert_allclose(
        out.mean(axis=(0, 1)), ref.mean(axis=(0, 1)), rtol=0.02
    )
    # Per-pixel: a few sigma of the 256-spp estimator.
    assert np.abs(out - ref).max() < 0.2, (
        f"max per-pixel diff {np.abs(out - ref).max():.3f}"
    )


# ---------------------------------------------------------------------------
# The sphere megakernel's contractions: one bf16 matmul for the hit's rows
# (PR 38), one bf16 pass for each K=3 contraction (PR 46).

_GATHER_SPHERES = 13  # padded to 16: three padded spheres


def _full_mantissa(rng, shape, scale):
    """float32 values that use every bit of the significand: an odd 24-bit
    integer times a power of two has no trailing zero bit."""
    odd = rng.integers(1 << 23, 1 << 24, size=shape, dtype=np.int64) | 1
    sign = rng.choice([-1.0, 1.0], size=shape)
    return (sign * odd * scale).astype(np.float32)


def _gather_tables():
    """Four per-sphere tables whose values use every bit of a float32
    significand, plus a zero, a 1e-3 and a 1e5 in each."""
    rng = np.random.default_rng(38)
    n = _GATHER_SPHERES
    tables = {
        "centre": _full_mantissa(rng, (3, n), 2.0 ** -20),
        "albedo": _full_mantissa(rng, (3, n), 2.0 ** -24),
        "emission": _full_mantissa(rng, (3, n), 2.0 ** -21),
        "radius": np.abs(_full_mantissa(rng, (1, n), 2.0 ** -23)),
    }
    for table in tables.values():
        table[0, 1], table[0, 2], table[0, 3] = 0.0, 1e-3, 1e5
    return tables


@pytest.mark.parametrize("which", ["centre", "albedo", "emission", "radius"])
def test_gather_hit_returns_table_columns_bit_for_bit(which):
    """One bf16 matmul against a one-hot returns the float32 tables'
    columns unchanged, for every index and for the padded spheres."""
    tables = _gather_tables()
    n = _GATHER_SPHERES
    padded_n = -(-n // 8) * 8
    padded = {
        name: np.pad(table, ((0, 0), (0, padded_n - n)))
        for name, table in tables.items()
    }
    table = pallas_kernels._gather_table(
        jnp.asarray(tables["centre"].T), jnp.asarray(tables["albedo"].T),
        jnp.asarray(tables["emission"].T), jnp.asarray(tables["radius"][0]),
        padded_n,
    )
    assert table.dtype == jnp.bfloat16
    assert table.shape == (3 * pallas_kernels._GATHER_ROWS, padded_n)
    lanes = 128
    idx = jnp.arange(lanes, dtype=jnp.int32)[None, :] % padded_n  # every index
    sphere_iota = jax.lax.broadcasted_iota(jnp.int32, (padded_n, lanes), 0)
    gathered = dict(zip(
        ("centre", "albedo", "emission", "radius"),
        pallas_kernels._gather_hit(table, sphere_iota, idx),
    ))
    got = np.asarray(gathered[which])
    want = padded[which][:, np.asarray(idx[0])]
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    assert not want[:, n:padded_n].any()  # a padded sphere reads zeros


def _k3_operands(ray_scale):
    """The `_gather_tables` centres (a 0.0, a 1e-3 and a 1e5 among them)
    padded to 16 spheres, and 128 ray-side vectors whose components use
    every bit of a float32 significand, with a zero component, a
    negative one and a zero vector among them."""
    centres = _gather_tables()["centre"]  # [3, 13]
    padded_n = -(-_GATHER_SPHERES // 8) * 8
    vectors = _full_mantissa(np.random.default_rng(46), (3, 128), ray_scale)
    vectors[1, 0] = 0.0
    vectors[2, 1] = -abs(vectors[2, 1])
    vectors[:, 2] = 0.0
    return centres, padded_n, vectors


@pytest.mark.parametrize(
    "ray_scale", [2.0 ** -24, 2.0 ** -14, 2.0 ** -44],
    ids=["directions", "origins_1e3", "tiny"],
)
def test_dot_k3_exact_is_as_near_the_float64_product_as_dot_f32(ray_scale):
    """The one-pass K=3 contraction lies within two float32 roundings of
    the magnitude sum of the float64 product, and no further from it than
    `_dot_f32` does on the same operands; padded spheres read zero."""
    centres, padded_n, vectors = _k3_operands(ray_scale)
    padded = np.pad(centres, ((0, 0), (0, padded_n - centres.shape[1])))
    stack = pallas_kernels._center_stack(jnp.asarray(centres.T), padded_n)
    got = np.asarray(pallas_kernels._dot_k3_exact(stack, jnp.asarray(vectors)))
    six_pass = np.asarray(pallas_kernels._dot_f32(
        jnp.asarray(padded), jnp.asarray(vectors), (((0,), (0,)), ((), ()))
    ))
    assert got.dtype == np.float32 and got.shape == (padded_n, 128)
    exact = padded.astype(np.float64).T @ vectors.astype(np.float64)
    # One rounding of a sum this large: half an ulp of sum |c_k v_k|.
    rounding = np.abs(padded.astype(np.float64)).T @ np.abs(
        vectors.astype(np.float64)
    ) * 2.0 ** -24
    error = np.abs(got - exact)
    assert (error <= 2.0 * rounding).all()
    assert not got[centres.shape[1]:].any()  # a padded sphere reads zero
    assert not got[:, 2].any()  # and so does a zero vector
    scale = np.where(rounding > 0.0, rounding, 1.0)
    assert (error / scale).max() <= (np.abs(six_pass - exact) / scale).max()
    assert (error / scale).mean() <= (np.abs(six_pass - exact) / scale).mean()


def test_center_stack_is_the_centres_bf16_parts_bit_for_bit():
    """`_center_stack`: bfloat16, [N_padded, K], three equal blocks of
    lo / mid / hi / zeros whose parts sum back to the float32 centres."""
    centres = _gather_tables()["centre"]
    n = centres.shape[1]
    padded_n = -(-n // 8) * 8
    stack = pallas_kernels._center_stack(jnp.asarray(centres.T), padded_n)
    assert stack.dtype == jnp.bfloat16
    assert stack.shape == (padded_n, pallas_kernels._K3_DEPTH)
    assert pallas_kernels._K3_DEPTH <= 128  # one pass of the MXU
    values = np.asarray(stack.astype(jnp.float32))
    block = pallas_kernels._K3_BLOCK
    for at in range(0, pallas_kernels._K3_DEPTH, block):
        np.testing.assert_array_equal(values[:, at:at + block], values[:, :block])
    lo, mid, hi = values[:, 0:3], values[:, 8:11], values[:, 16:19]
    want = np.pad(centres.T, ((0, padded_n - n), (0, 0)))
    np.testing.assert_array_equal(
        ((hi + mid) + lo).view(np.uint32), want.view(np.uint32)
    )
    used = np.zeros(block, bool)
    used[[0, 1, 2, 8, 9, 10, 16, 17, 18]] = True
    assert not values[:, :block][:, ~used].any()
    assert not values[n:].any()  # padded spheres
    # smallest parts first: the accumulator adds along K
    assert (np.abs(lo) <= np.abs(mid)).all() and (np.abs(mid) <= np.abs(hi)).all()


def _parent_formulation(scene, origins, directions, seed, *, max_bounces):
    """The megakernel's bounce loop as PR 37 had it, in plain jnp over the
    whole ray set: six ``_dot_f32`` contractions a bounce (c . d, c . o,
    c . shadow_o and three one-hot gathers), the radius gathered on the
    VPU, c . o made anew from the origin every bounce."""
    dot = pallas_kernels._dot_f32
    contract_first = (((0,), (0,)), ((), ()))
    gather = (((1,), (0,)), ((), ()))
    n = scene.centers.shape[0]
    n_padded = -(-n // 8) * 8
    pad = n_padded - n
    c = jnp.pad(scene.centers, ((0, pad), (0, 0))).T
    radius = jnp.pad(scene.radii, (0, pad))[:, None]
    r2 = radius * radius
    csq = jnp.sum(c * c, axis=0)[:, None]
    albedo_t = jnp.pad(scene.albedo, ((0, pad), (0, 0))).T
    emission_t = jnp.pad(scene.emission, ((0, pad), (0, 0))).T
    dc_sun = pallas_kernels._center_dot_sun(c, scene.sun_direction)
    sun = scene.sun_direction[:, None]
    sun_color = scene.sun_color[:, None]
    sky_horizon = scene.sky_horizon[:, None]
    sky_zenith = scene.sky_zenith[:, None]
    plane_a = scene.plane_albedo_a[:, None]
    plane_b = scene.plane_albedo_b[:, None]

    o, d = origins.T, directions.T
    rays = o.shape[1]
    seed = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    ray_index = jnp.arange(rays, dtype=jnp.uint32)[None, :]
    sphere_iota = jax.lax.broadcasted_iota(jnp.int32, (n_padded, rays), 0)
    throughput = jnp.ones((3, rays), jnp.float32)
    radiance = jnp.zeros((3, rays), jnp.float32)
    alive = jnp.ones((1, rays), jnp.float32)
    for bounce in range(max_bounces):
        dc = dot(c, d, contract_first)
        oc = dot(c, o, contract_first)
        od = jnp.sum(o * d, axis=0, keepdims=True)
        o_sq = jnp.sum(o * o, axis=0, keepdims=True)
        oc_dot_d = dc - od
        oc_sq = o_sq - 2.0 * oc + csq
        disc = oc_dot_d * oc_dot_d - (oc_sq - r2)
        valid = (disc > 0.0) & (r2 > 0.0)
        sqrt_disc = jnp.sqrt(jnp.maximum(disc, 0.0))
        t0 = oc_dot_d - sqrt_disc
        t1 = oc_dot_d + sqrt_disc
        t_all = jnp.where(t0 > EPS, t0, jnp.where(t1 > EPS, t1, INF))
        t_all = jnp.where(valid, t_all, INF)
        t_sphere = jnp.min(t_all, axis=0, keepdims=True)
        idx = jnp.min(
            jnp.where(t_all == t_sphere, sphere_iota, n_padded),
            axis=0, keepdims=True,
        )
        idx = jnp.minimum(idx, n_padded - 1)
        d_y, o_y = d[1:2, :], o[1:2, :]
        denom = jnp.where(jnp.abs(d_y) < 1e-8, 1e-8, d_y)
        t_plane = -o_y / denom
        t_plane = jnp.where(
            (t_plane > EPS) & (jnp.abs(d_y) >= 1e-8), t_plane, INF
        )
        is_plane = (t_plane < t_sphere).astype(jnp.float32)
        t = jnp.minimum(t_sphere, t_plane)
        hit = (t < INF).astype(jnp.float32)
        blend = jnp.clip(d[1:2, :], 0.0, 1.0)
        sun_cos_dir = jnp.sum(d * sun, axis=0, keepdims=True)
        sun_disc = jnp.where(sun_cos_dir > 0.9995, 8.0, 0.0)
        sky = (1.0 - blend) * sky_horizon + blend * sky_zenith
        sky = sky + sun_disc * sun_color
        radiance = radiance + throughput * sky * (alive * (1.0 - hit))
        alive = alive * hit
        p = o + d * t
        one_hot = (sphere_iota == idx).astype(jnp.float32)
        c_hit = dot(c, one_hot, gather)
        r_hit = jnp.sum(radius * one_hot, axis=0, keepdims=True)
        albedo_hit = dot(albedo_t, one_hot, gather)
        emission_hit = dot(emission_t, one_hot, gather)
        sphere_normal = (p - c_hit) / jnp.maximum(r_hit, 1e-6)
        plane_normal = jnp.concatenate(
            [jnp.zeros((1, rays)), jnp.ones((1, rays)), jnp.zeros((1, rays))]
        ).astype(jnp.float32)
        normal = is_plane * plane_normal + (1.0 - is_plane) * sphere_normal
        checker = (
            jnp.floor(p[0:1, :]).astype(jnp.int32)
            + jnp.floor(p[2:3, :]).astype(jnp.int32)
        ) % 2
        checker_rgb = jnp.where(checker == 0, plane_a, plane_b)
        albedo = is_plane * checker_rgb + (1.0 - is_plane) * albedo_hit
        emission = (1.0 - is_plane) * emission_hit
        radiance = radiance + throughput * emission * alive
        shadow_o = p + normal * (EPS * 4.0)
        oc_s = dot(c, shadow_o, contract_first)
        od_s = jnp.sum(shadow_o * sun, axis=0, keepdims=True)
        osq_s = jnp.sum(shadow_o * shadow_o, axis=0, keepdims=True)
        ocd_s = dc_sun - od_s
        ocsq_s = osq_s - 2.0 * oc_s + csq
        disc_s = ocd_s * ocd_s - (ocsq_s - r2)
        valid_s = (disc_s > 0.0) & (r2 > 0.0)
        t1_s = ocd_s + jnp.sqrt(jnp.maximum(disc_s, 0.0))
        shadowed = jnp.max(
            jnp.where(valid_s & (t1_s > EPS), 1.0, 0.0), axis=0, keepdims=True
        )
        cos_sun = jnp.maximum(jnp.sum(normal * sun, axis=0, keepdims=True), 0.0)
        direct = (
            albedo * sun_color * (cos_sun * (1.0 - shadowed) * alive)
            / jnp.float32(jnp.pi)
        )
        radiance = radiance + throughput * direct
        throughput = throughput * (alive * albedo + (1.0 - alive))
        counter = (
            ray_index * jnp.uint32(2 * max_bounces + 2)
            + jnp.uint32(2 * bounce)
        )
        u1 = pallas_kernels._uniform_from_hash(
            pallas_kernels._pcg_hash(counter ^ seed)
        )
        u2 = pallas_kernels._uniform_from_hash(
            pallas_kernels._pcg_hash((counter + jnp.uint32(1)) ^ seed)
        )
        r = jnp.sqrt(u1)
        phi = jnp.float32(2.0 * jnp.pi) * u2
        x, y = r * jnp.cos(phi), r * jnp.sin(phi)
        z = jnp.sqrt(jnp.maximum(0.0, 1.0 - u1))
        helper_x = jnp.where(jnp.abs(normal[0:1, :]) > 0.9, 0.0, 1.0)
        helper_y = 1.0 - helper_x
        tangent = jnp.concatenate([
            helper_y * normal[2:3, :],
            -helper_x * normal[2:3, :],
            helper_x * normal[1:2, :] - helper_y * normal[0:1, :],
        ])
        tangent = tangent / jnp.maximum(
            jnp.sqrt(jnp.sum(tangent * tangent, axis=0, keepdims=True)), 1e-8
        )
        bitangent = jnp.concatenate([
            normal[1:2, :] * tangent[2:3, :] - normal[2:3, :] * tangent[1:2, :],
            normal[2:3, :] * tangent[0:1, :] - normal[0:1, :] * tangent[2:3, :],
            normal[0:1, :] * tangent[1:2, :] - normal[1:2, :] * tangent[0:1, :],
        ])
        new_d = x * tangent + y * bitangent + z * normal
        new_o = p + normal * (EPS * 4.0)
        live = alive > 0.5
        o = jnp.where(live, new_o, o)
        d = jnp.where(live, new_d, d)
    return radiance.T


def _frame_rays(size=64, samples=2, frame=1):
    from tpu_render_cluster.render.integrator import (
        flat_sample_rays, tile_base_key, tile_trace_key, trace_seed,
    )

    base_key = tile_base_key(jnp.int32(frame), 0, 0)
    origins, directions = flat_sample_rays(
        scene_camera("04_very-simple", frame), base_key, width=size,
        height=size, y0=0, x0=0, tile_height=size, tile_width=size,
        samples=samples,
    )
    return origins, directions, trace_seed(tile_trace_key(base_key))


def test_megakernel_matches_parent_formulation():
    """4 bounces of 64x64x2spp of 04_very-simple: the one-matmul gather
    and the one-pass K=3 contractions against six `_dot_f32` contractions
    a bounce. The gather changes no value; the one-pass contraction
    rounds its sums in another order than `_dot_f32`, so a ray in 25
    takes another turn somewhere along its path (a hit an ulp before or
    behind a silhouette or the shadow's edge) and the rest agree to the
    compiler's rounding. Read on the CPU interpreter: 96.0% of rays
    within `rtol=1e-5` (with the large parts first along K: 92.7%), the
    image means equal to 8e-6."""
    scene = build_scene("04_very-simple", 1)
    origins, directions, seed = _frame_rays()
    want = np.asarray(
        jax.jit(_parent_formulation, static_argnames="max_bounces")(
            scene, origins, directions, seed, max_bounces=4
        )
    )
    got = np.asarray(
        trace_paths_fused(scene, origins, directions, seed, max_bounces=4)
    )
    assert got.shape == want.shape == (64 * 64 * 2, 3)
    assert want.max() > 0.1  # a picture, not a black frame
    assert np.isfinite(got).all()
    same_turns = np.isclose(got, want, rtol=1e-5, atol=1e-6).all(axis=1)
    assert same_turns.mean() >= 0.94
    means = got.mean(dtype=np.float64), want.mean(dtype=np.float64)
    assert abs(means[0] - means[1]) <= 1e-4 * means[1]


def test_megakernel_lane_rows_match_whole_frame_bit_for_bit():
    """The `lane_io` form on a cropped region, fed the full frame's lane
    ids, computes the whole-frame form's values on those lanes."""
    scene = build_scene("04_very-simple", 1)
    origins, directions, seed = _frame_rays()
    whole = np.asarray(
        trace_paths_fused(scene, origins, directions, seed, max_bounces=4)
    )
    size, samples = 64, 2
    ys, xs = np.meshgrid(np.arange(17, 49), np.arange(9, 41), indexing="ij")
    pixels = (ys * size + xs).reshape(-1)
    lanes = (np.arange(samples)[:, None] * size * size + pixels[None, :]).reshape(-1)
    region = np.asarray(
        trace_paths_fused(
            scene, origins[lanes], directions[lanes], seed, max_bounces=4,
            lane=jnp.asarray(lanes, jnp.int32),
        )
    )
    np.testing.assert_array_equal(
        region.view(np.uint32), whole[lanes].view(np.uint32)
    )


@pytest.mark.parametrize("fate", ["all_miss", "die_at_bounce_1"])
def test_megakernel_dead_lanes_stay_finite(fate):
    """The carried origin and direction of a lane that has left the
    scene stay finite: a NaN there reaches `cos_sun` through `p`, and
    NaN * 0 is NaN."""
    scene = build_scene("04_very-simple", 1)
    n = 256
    if fate == "all_miss":
        origin, direction = [0.0, 50.0, 0.0], [0.0, 1.0, 0.0]
    else:
        # Straight down onto the plane a kilometre from the spheres: the
        # resampled direction points up and nothing is above.
        origin, direction = [1000.0, 50.0, 1000.0], [0.0, -1.0, 0.0]
    origins = jnp.tile(jnp.asarray([origin], jnp.float32), (n, 1))
    origins = origins + jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.asarray(
        [[0.25, 0.0, 0.125]], jnp.float32
    )
    directions = jnp.tile(jnp.asarray([direction], jnp.float32), (n, 1))
    for lane in (None, jnp.arange(n, dtype=jnp.int32) * 7):
        out = np.asarray(
            trace_paths_fused(scene, origins, directions, 11, max_bounces=4, lane=lane)
        )
        assert np.isfinite(out).all()
        assert (out > 0.0).all()  # sky, or sunlit ground then sky
    if fate == "die_at_bounce_1":
        # one bounce sees the ground alone; four add the sky above it
        first = np.asarray(
            trace_paths_fused(scene, origins, directions, 11, max_bounces=1)
        )
        assert (out.sum(axis=1) > first.sum(axis=1)).all()
