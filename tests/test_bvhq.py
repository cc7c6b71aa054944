"""Quantized node formats + SAH/wide BLAS builds (ISSUE 15).

Contracts pinned here:

1. CONSERVATIVE CONTAINMENT — a quantized node AABB, reconstructed with
   the kernels' exact f32 arithmetic (``origin + q * cell``), always
   CONTAINS its fp32 original, on randomized and degenerate (flat /
   tiny-span / far-offset) node sets, for both quant tiers; the packed
   meta word round-trips skip/first/count exactly.
2. NEVER-MISS — one fused bounce (nearest + NEE shadow any-hits +
   shading + key epilogue) through the quantized kernels is BIT-IDENTICAL
   to the fp32 walk, TLAS and flat, on randomized/degenerate fields: the
   quantized walk visits a superset of nodes and triangle tests stay
   exact f32, so no hit can be lost and strict-< best-t updates keep tie
   winners.
3. SAH/wide builds are well-formed drop-ins: the threaded arrays satisfy
   the preorder/skip invariants at any arity, traversal equals the
   brute-force reference, and the masked-tier image is uint8-identical
   to the median build's (per-lane results are visit-order invariant).
4. Recompile/caching bounds: one compile per (quant, builder)
   config — frames 2..3 add nothing (the test_tlas idiom) — and the
   geometry cache / renderer caches key on the build knobs so an env
   toggle can never serve a stale tree.

Interpret mode on CPU is slow, so shapes are tiny (kernel launches still
span real blocks — ray counts pad to the kernel block internally).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TRC_PALLAS", "0")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

pytestmark = pytest.mark.bvhq

DEEP_SCENE = "03_physics-2-mesh"
SHALLOW_SCENE = "02_physics-mesh"


# -- quantization property ----------------------------------------------------


def _node_sets():
    rng = np.random.default_rng(41)
    sets = []
    # Randomized spread-out boxes.
    lo = rng.uniform(-20, 20, (64, 3)).astype(np.float32)
    sets.append(("random", lo, lo + rng.uniform(0.01, 8.0, (64, 3)).astype(np.float32)))
    # Degenerate: all boxes identical (flat union window).
    one = np.tile(np.array([[3.0, -2.0, 7.0]], np.float32), (8, 1))
    sets.append(("identical", one, one + 1.0))
    # Degenerate: zero-extent boxes (points).
    pts = rng.uniform(-5, 5, (16, 3)).astype(np.float32)
    sets.append(("points", pts, pts.copy()))
    # Tiny span at a large offset — the worst case for f32 reconstruction
    # rounding (cells near the coordinate ulp).
    base = np.full((32, 3), 1000.0, np.float32)
    jitter = rng.uniform(0, 1e-4, (32, 3)).astype(np.float32)
    sets.append(("far-tiny", base + jitter, base + jitter + 1e-5))
    # Single node.
    sets.append(
        ("single", np.array([[-1.0, -2.0, -3.0]], np.float32),
         np.array([[4.0, 5.0, 6.0]], np.float32))
    )
    return sets


@pytest.mark.parametrize("quant", [1, 2])
def test_quantized_bounds_conservatively_contain_fp32(quant):
    from tpu_render_cluster.render.mesh import (
        LEAF_SIZE,
        dequantize_node_bounds,
        quantize_node_tables,
        unpack_node_meta,
    )

    rng = np.random.default_rng(7)
    for name, lo, hi in _node_sets():
        n = lo.shape[0]
        skip = rng.integers(1, n + 1, n).astype(np.int32)
        first = (rng.integers(0, 64, n) * LEAF_SIZE).astype(np.int32)
        count = rng.integers(0, LEAF_SIZE + 1, n).astype(np.int32)
        bq, meta, grid = quantize_node_tables(
            lo, hi, skip, first, count, quant=quant, first_unit=LEAF_SIZE
        )
        rlo, rhi = dequantize_node_bounds(
            jnp.asarray(bq), jnp.asarray(grid), quant
        )
        rlo, rhi = np.asarray(rlo), np.asarray(rhi)
        assert (rlo <= lo).all(), f"{name}: quantized lo not conservative"
        assert (rhi >= hi).all(), f"{name}: quantized hi not conservative"
        s, f, c = unpack_node_meta(np.asarray(meta), first_unit=LEAF_SIZE)
        np.testing.assert_array_equal(np.asarray(s), skip, err_msg=name)
        np.testing.assert_array_equal(np.asarray(f), first, err_msg=name)
        np.testing.assert_array_equal(np.asarray(c), count, err_msg=name)


def test_quantized_slab_hits_are_a_superset():
    """Any exact slab hit is also a quantized-slab hit (never-miss at the
    single-node level): follows from containment, pinned directly on
    randomized rays so a reconstruction regression fails loudly."""
    from tpu_render_cluster.render.mesh import (
        dequantize_node_bounds,
        quantize_node_tables,
    )

    rng = np.random.default_rng(11)
    lo = rng.uniform(-10, 10, (48, 3)).astype(np.float32)
    hi = lo + rng.uniform(0.05, 4.0, (48, 3)).astype(np.float32)
    zeros = np.zeros(48, np.int32)
    origins = rng.uniform(-15, 15, (256, 3)).astype(np.float32)
    directions = rng.normal(size=(256, 3)).astype(np.float32)
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    inv = 1.0 / np.where(np.abs(directions) < 1e-12, 1e-12, directions)

    def slab_hits(blo, bhi):
        t0 = (blo[None] - origins[:, None]) * inv[:, None]
        t1 = (bhi[None] - origins[:, None]) * inv[:, None]
        near = np.minimum(t0, t1).max(axis=2)
        far = np.maximum(t0, t1).min(axis=2)
        return far >= np.maximum(near, 0.0)

    exact = slab_hits(lo, hi)
    for quant in (1, 2):
        bq, _meta, grid = quantize_node_tables(
            lo, hi, zeros, zeros, zeros, quant=quant, first_unit=1
        )
        rlo, rhi = dequantize_node_bounds(
            jnp.asarray(bq), jnp.asarray(grid), quant
        )
        quantized = slab_hits(np.asarray(rlo), np.asarray(rhi))
        assert (quantized | ~exact).all(), f"tier {quant} lost a hit"


def test_resolve_bvh_quant_degrades_on_range_overflow():
    from tpu_render_cluster.render import pallas_kernels as pk

    assert pk.resolve_bvh_quant(0, (10, 10, 16)) == 0
    assert pk.resolve_bvh_quant(1, (10, 10, 16)) == 1
    assert pk.resolve_bvh_quant(2, (10, 10, 16), (30, 40, 4)) == 2
    # Any table outgrowing the packed meta ranges degrades the whole
    # kernel to the fp32 format.
    assert pk.resolve_bvh_quant(1, (1 << 17, 10, 16)) == 0
    assert pk.resolve_bvh_quant(1, (10, 1 << 12, 16)) == 0
    assert pk.resolve_bvh_quant(1, (10, 10, 64)) == 0
    assert pk.resolve_bvh_quant(1, (10, 10, 16), (1 << 17, 1, 1)) == 0


def test_bvh_env_tier_resolution(monkeypatch):
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.mesh import bvh_builder, bvh_wide

    for name in ("TRC_BVH_QUANT", "TRC_BVH_BUILDER", "TRC_BVH_WIDE"):
        monkeypatch.delenv(name, raising=False)
    assert pk.bvh_quant_mode() == 0  # default off (exact baseline)
    assert bvh_builder() == "sah"  # defaults ship the exact wins on
    assert bvh_wide() == 4
    monkeypatch.setenv("TRC_BVH_QUANT", "2")
    monkeypatch.setenv("TRC_BVH_BUILDER", "median")
    monkeypatch.setenv("TRC_BVH_WIDE", "1")
    assert pk.bvh_quant_mode() == 2
    assert bvh_builder() == "median"
    assert bvh_wide() == 1
    # Out-of-range / junk values clamp or fall back, never raise.
    monkeypatch.setenv("TRC_BVH_QUANT", "9")
    monkeypatch.setenv("TRC_BVH_BUILDER", "octree")
    monkeypatch.setenv("TRC_BVH_WIDE", "99")
    assert pk.bvh_quant_mode() == 2
    assert bvh_builder() == "sah"
    assert bvh_wide() == 8


# -- SAH / wide builds --------------------------------------------------------


@pytest.mark.parametrize("builder", ["median", "sah"])
@pytest.mark.parametrize("wide", [1, 4, 8])
def test_builds_are_wellformed_and_match_brute_force(builder, wide):
    from tpu_render_cluster.render.mesh import (
        LEAF_SIZE,
        build_bvh,
        intersect_bvh_packet,
        intersect_triangles_brute,
        make_icosphere,
    )

    bvh = build_bvh(*make_icosphere(2), builder=builder, wide=wide)
    skip = np.asarray(bvh.skip)
    count = np.asarray(bvh.count)
    first = np.asarray(bvh.first)
    n = skip.shape[0]
    # Threaded preorder invariants at any arity.
    assert (skip > np.arange(n)).all()
    assert (skip <= n).all()
    assert (first % LEAF_SIZE == 0).all()
    visited, node = [], 0
    while node < n:
        visited.append(node)
        node = int(skip[node]) if count[node] > 0 else node + 1
    assert visited == list(range(n))
    assert count.sum() == 320  # icosphere(2) triangles, each in one leaf
    # Traversal equals brute force on randomized rays (the correctness
    # reference): the build changed only array contents, not semantics.
    rng = np.random.default_rng(17)
    origins = rng.uniform(-1.2, 1.2, (128, 3)).astype(np.float32)
    directions = rng.normal(size=(128, 3)).astype(np.float32)
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    t_walk, _ = intersect_bvh_packet(
        bvh, jnp.asarray(origins), jnp.asarray(directions)
    )
    t_brute, _ = intersect_triangles_brute(
        bvh, jnp.asarray(origins), jnp.asarray(directions)
    )
    t_walk, t_brute = np.asarray(t_walk), np.asarray(t_brute)
    # Same hit set; t within XLA fusion noise (the brute reference runs
    # one [R, T] pass, the walk [R, LEAF_SIZE] slices — ulp-level op
    # reassociation, not a traversal difference).
    np.testing.assert_array_equal(t_walk == np.float32(1e30),
                                  t_brute == np.float32(1e30))
    np.testing.assert_allclose(t_walk, t_brute, rtol=1e-5, atol=0)


def test_sah_build_visits_fewer_nodes():
    """The point of the SAH/wide build: fewer nodes and fuller leaves
    than the median split on the deep scene's BLAS."""
    from tpu_render_cluster.render.mesh import build_bvh, make_icosphere

    median = build_bvh(*make_icosphere(2), builder="median", wide=1)
    sah = build_bvh(*make_icosphere(2), builder="sah", wide=4)
    assert sah.skip.shape[0] < median.skip.shape[0]
    m_count = np.asarray(median.count)
    s_count = np.asarray(sah.count)
    assert (s_count > 0).sum() < (m_count > 0).sum()
    assert s_count[s_count > 0].mean() > m_count[m_count > 0].mean()


def test_geometry_cache_keyed_on_build_params():
    from tpu_render_cluster.render.mesh import (
        cached_mesh_bvh,
        reset_geometry_cache,
    )

    reset_geometry_cache()
    sah4 = cached_mesh_bvh("icosphere", "sah", 4)
    assert cached_mesh_bvh("icosphere", "sah", 4) is sah4  # memoized
    median = cached_mesh_bvh("icosphere", "median", 1)
    assert median is not sah4
    assert median.skip.shape[0] != sah4.skip.shape[0]
    # A distinct arity is a distinct build.
    assert cached_mesh_bvh("icosphere", "sah", 8) is not sah4


def test_renderer_cache_keys_on_env_tiers(monkeypatch):
    """Toggling TRC_BVH_BUILDER / TRC_BVH_QUANT mid-process resolves to a
    DIFFERENT cached renderer (fresh tree + kernel), never a stale hit."""
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    monkeypatch.setenv("TRC_BVH_BUILDER", "median")
    monkeypatch.setenv("TRC_BVH_WIDE", "1")
    monkeypatch.setenv("TRC_BVH_QUANT", "0")
    a = fused_frame_renderer(DEEP_SCENE, 8, 8, 1, 2)
    monkeypatch.setenv("TRC_BVH_BUILDER", "sah")
    monkeypatch.setenv("TRC_BVH_WIDE", "4")
    b = fused_frame_renderer(DEEP_SCENE, 8, 8, 1, 2)
    monkeypatch.setenv("TRC_BVH_QUANT", "1")
    c = fused_frame_renderer(DEEP_SCENE, 8, 8, 1, 2)
    assert a is not b and b is not c
    # Same env resolves to the same cached renderer.
    assert fused_frame_renderer(DEEP_SCENE, 8, 8, 1, 2) is c


# -- kernel never-miss (per tier) --------------------------------------------


def _random_field(seed: int, k: int, builder="sah", wide=4):
    from tpu_render_cluster.render.mesh import (
        MeshInstances,
        MeshSet,
        cached_mesh_bvh,
        rotation_y,
    )

    rng = np.random.default_rng(seed)
    rotation = jax.vmap(rotation_y)(
        jnp.asarray(rng.uniform(0, 2 * np.pi, k).astype(np.float32))
    )
    return MeshSet(
        bvh=cached_mesh_bvh("icosphere", builder, wide),
        instances=MeshInstances(
            rotation=rotation,
            translation=jnp.asarray(
                rng.uniform(-4, 4, (k, 3)).astype(np.float32)
            ),
            albedo=jnp.asarray(
                rng.uniform(0.2, 0.9, (k, 3)).astype(np.float32)
            ),
            scale=jnp.asarray(rng.uniform(0.4, 1.2, k).astype(np.float32)),
        ),
    )


def _overlapping_field(k: int):
    from tpu_render_cluster.render.mesh import (
        MeshInstances,
        MeshSet,
        cached_mesh_bvh,
    )

    return MeshSet(
        bvh=cached_mesh_bvh("icosphere", "sah", 4),
        instances=MeshInstances(
            rotation=jnp.tile(jnp.eye(3, dtype=jnp.float32), (k, 1, 1)),
            translation=jnp.tile(
                jnp.asarray([[0.5, 1.0, -0.25]], jnp.float32), (k, 1)
            ),
            albedo=jnp.tile(
                jnp.asarray([[0.6, 0.5, 0.4]], jnp.float32), (k, 1)
            ),
            scale=jnp.ones((k,), jnp.float32),
        ),
    )


def _bounce_state(seed: int, n: int):
    rng = np.random.default_rng(seed)
    origins = rng.uniform(-5, 5, (n, 3)).astype(np.float32)
    origins[:, 1] = rng.uniform(0.5, 6.0, n).astype(np.float32)
    directions = rng.normal(size=(n, 3)).astype(np.float32)
    directions[:, 1] -= 1.0
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    return jnp.asarray(origins), jnp.asarray(directions)


def _one_bounce(mesh, origins, directions, *, use_tlas, quant):
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene(DEEP_SCENE, 5)
    n = origins.shape[0]
    throughput = jnp.ones((n, 3), jnp.float32)
    alive = jnp.ones((n,), bool)
    return pk.mesh_bounce_pallas(
        scene, mesh, origins, directions, throughput, alive,
        jnp.int32(1234), 0, total_bounces=4,
        live_count=jnp.int32(n), use_tlas=use_tlas, quant=quant,
    )


@pytest.mark.parametrize("use_tlas", [False, True])
@pytest.mark.parametrize("field", ["random-12", "overlapping-8"])
def test_quantized_kernels_never_miss_vs_fp32(monkeypatch, use_tlas, field):
    """One fused bounce, quantized vs fp32 node tables, TLAS and flat:
    EVERY output (incl. the fused key column) is bit-identical — the
    conservative cull can only add node visits, and strict-< best-t
    updates on exact triangle tests keep every winner."""
    monkeypatch.setenv("TRC_PALLAS", "1")
    mesh = (
        _random_field(11, 12) if field == "random-12"
        else _overlapping_field(8)
    )
    origins, directions = _bounce_state(29, 256)
    base = _one_bounce(mesh, origins, directions, use_tlas=use_tlas, quant=0)
    for quant in (1, 2):
        out = _one_bounce(
            mesh, origins, directions, use_tlas=use_tlas, quant=quant
        )
        labels = ("contribution", "origins", "directions", "throughput",
                  "alive")
        for name, a, b in zip(labels, base[:5], out[:5]):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"tlas={use_tlas} quant={quant}: {name} diverged",
            )
        # The key column: everything but the candidate bits [18:24)
        # matches bit for bit. The quant tiers deliberately source the
        # candidate from the nearest walk's winning instance (no second
        # TLAS walk) instead of the fp32 epilogue's entry walk — keys
        # only order lanes, so the payload outputs above stay exact.
        if base[5] is None:
            assert out[5] is None
        else:
            cand_mask = ~np.int32(0x3F << 18)
            np.testing.assert_array_equal(
                np.asarray(base[5]) & cand_mask,
                np.asarray(out[5]) & cand_mask,
                err_msg=f"tlas={use_tlas} quant={quant}: key diverged",
            )


# -- per-tier image equivalence ----------------------------------------------


def _masked_uint8(scene_name, quant, builder, wide, **kwargs):
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    renderer = fused_frame_renderer(
        scene_name, kwargs["width"], kwargs["height"], kwargs["samples"],
        kwargs["max_bounces"], None, quant, builder, wide,
    )
    return np.asarray(renderer(30))


@pytest.mark.parametrize("scene_name", [DEEP_SCENE, SHALLOW_SCENE])
def test_masked_images_identical_across_node_formats(monkeypatch, scene_name):
    """SAH-vs-median image equivalence AND quantized-vs-fp32, masked
    tier: the tonemapped uint8 frame is IDENTICAL across every node
    format (deep per-bounce path for 03, fused megakernel for 02). All
    variants coexist as distinct compiled programs — the property the
    interleaved A/B bench relies on."""
    monkeypatch.setenv("TRC_PALLAS", "1")
    kwargs = dict(width=12, height=12, samples=1, max_bounces=2)
    reference = _masked_uint8(scene_name, 0, "median", 1, **kwargs)
    for quant, builder, wide in (
        (0, "sah", 4), (1, "median", 1), (2, "sah", 4), (1, "sah", 8),
    ):
        image = _masked_uint8(scene_name, quant, builder, wide, **kwargs)
        np.testing.assert_array_equal(
            reference, image,
            err_msg=f"quant={quant} builder={builder} wide={wide}",
        )


# -- recompile bounds ---------------------------------------------------------


def test_one_compile_per_quant_builder_config(monkeypatch):
    """Three frames per (quant, builder) config: the renderer factory
    builds one program on frame 1 — frames 2..3 add nothing to
    render_compiles_total, and a SECOND config adds its own (a distinct
    program), extending the test_tlas.py idiom to the node-format axis."""
    from tpu_render_cluster.obs import render_compile_counter
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    monkeypatch.setenv("TRC_PALLAS", "1")
    fused_frame_renderer.cache_clear()
    counter = render_compile_counter()

    def frames(quant, builder, indices):
        before = counter.value()
        for frame in indices:
            renderer = fused_frame_renderer(
                DEEP_SCENE, 8, 8, 1, 2, None, quant, builder, 4, with_live=True
            )
            np.asarray(renderer(frame)[0])
        return counter.value() - before

    assert frames(1, "sah", (30, 31, 32)) == 1
    assert frames(0, "sah", (30, 31)) == 1  # the other quant tier: its own program
    assert frames(1, "sah", (33,)) == 0  # the first config is still there


# -- on-chip sweep ------------------------------------------------------------


@pytest.mark.slow
def test_on_chip_quant_sah_sweep():
    """Bigger-shape sweep across node formats (slow-marked like the other
    kernel suites; tier-1 runs the tiny-shape suite above)."""
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    reference = None
    for quant, builder, wide in (
        (0, "median", 1), (0, "sah", 4), (1, "sah", 4), (2, "sah", 4),
    ):
        renderer = fused_frame_renderer(
            DEEP_SCENE, 64, 64, 2, 4, None, quant, builder, wide
        )
        image = np.asarray(renderer(12))
        if reference is None:
            reference = image
        else:
            np.testing.assert_array_equal(reference, image)
