"""Two-level BVH traversal tests (ISSUE 10): TLAS over instances.

Contracts pinned here:

1. TLAS topology invariants — the threaded skip-link median split over
   instance slots is a well-formed DFS-preorder tree whose leaves
   partition the slot range, for every field size incl. the degenerate
   1-instance field.
2. TLAS-vs-flat numeric equivalence at the KERNEL level on randomized
   instance fields (one fused bounce = nearest walk + NEE shadow
   any-hits + shading), incl. a degenerate all-overlapping field and a
   1-instance field (which auto-degrades to the flat sweep).
3. Image equivalence: the frame is uint8-identical TLAS vs flat —
   per-lane results are instance-visit-order invariant, so the
   hierarchy may only change packet-cull efficiency, never pixels.
4. The fused coherence-key epilogue is bit-identical to its XLA twin
   (``mesh_sort_keys``) — the one-derivation contract that lets bounce
   0 key through XLA and bounces 1+ read the kernel's column.
5. Compile/build bounds: TLAS topologies are memoized per
   (instance count, leaf size) — never rebuilt per frame — and the
   TLAS kernels add no per-frame compiles.

Interpret mode on CPU is slow, so shapes are tiny (every kernel launch
still spans real blocks — ray counts pad to BVH_BLOCK_R internally).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TRC_PALLAS", "0")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

pytestmark = pytest.mark.tlas

DEEP_SCENE = "03_physics-2-mesh"  # 127-node BLAS x 48 instances
SHALLOW_SCENE = "02_physics-mesh"  # 3-node BLAS x 24 instances (megakernel)


# -- topology ----------------------------------------------------------------


@pytest.mark.parametrize("k_count", [1, 2, 3, 5, 8, 24, 48])
@pytest.mark.parametrize("leaf_size", [1, 4])
def test_tlas_topology_invariants(k_count, leaf_size):
    from tpu_render_cluster.render.mesh import build_tlas_topology

    topology = build_tlas_topology(k_count, leaf_size)
    m = topology.skip.shape[0]
    assert topology.first.shape == (m,)
    assert topology.count.shape == (m,)
    assert topology.member.shape == (m, k_count)
    # Root covers everything; every node's skip jumps strictly forward.
    assert topology.member[0].all()
    assert (topology.skip > np.arange(m)).all()
    assert (topology.skip <= m).all()
    # Leaves partition the slot range exactly once.
    covered = np.zeros(k_count, int)
    for i in range(m):
        cnt = int(topology.count[i])
        if cnt > 0:
            lo = int(topology.first[i])
            assert cnt <= leaf_size
            covered[lo:lo + cnt] += 1
            # A leaf's member mask is exactly its slot range.
            expect = np.zeros(k_count, bool)
            expect[lo:lo + cnt] = True
            assert (topology.member[i] == expect).all()
    assert (covered == 1).all()
    # The skip-link walk that descends everywhere visits every node in
    # preorder: node i's "hit" successor is i+1 (inner) or skip (leaf).
    visited = []
    node = 0
    while node < m:
        visited.append(node)
        node = (
            int(topology.skip[node])
            if int(topology.count[node]) > 0 else node + 1
        )
    assert visited == list(range(m))
    assert topology.depth >= 1


def test_tlas_topology_rejects_empty_field():
    from tpu_render_cluster.render.mesh import build_tlas_topology

    with pytest.raises(ValueError):
        build_tlas_topology(0, 4)


def test_cached_tlas_topology_memoizes_and_resets():
    from tpu_render_cluster.render.mesh import (
        cached_tlas_topology,
        reset_geometry_cache,
        tlas_build_counter,
    )

    reset_geometry_cache()
    before = tlas_build_counter().value()
    first = cached_tlas_topology(48, 4)
    assert cached_tlas_topology(48, 4) is first  # memoized, no rebuild
    assert tlas_build_counter().value() == before + 1
    # A distinct (k, leaf) is a distinct build...
    assert cached_tlas_topology(48, 8) is not first
    assert tlas_build_counter().value() == before + 2
    # ...and reset makes the next call rebuild (test isolation hook).
    reset_geometry_cache()
    assert cached_tlas_topology(48, 4) is not first
    assert tlas_build_counter().value() == before + 3


def test_cached_mesh_bvh_memoizes_and_resets():
    from tpu_render_cluster.render.mesh import (
        cached_mesh_bvh,
        reset_geometry_cache,
    )

    reset_geometry_cache()
    first = cached_mesh_bvh("box")
    assert cached_mesh_bvh("box") is first
    reset_geometry_cache()
    assert cached_mesh_bvh("box") is not first
    with pytest.raises(ValueError):
        cached_mesh_bvh("dodecahedron")


def test_tlas_node_bounds_are_member_unions():
    from tpu_render_cluster.render.mesh import (
        build_tlas_topology,
        tlas_node_bounds,
    )

    rng = np.random.default_rng(7)
    k = 11
    lo = rng.uniform(-5, 4, (k, 3)).astype(np.float32)
    hi = lo + rng.uniform(0.1, 2.0, (k, 3)).astype(np.float32)
    topology = build_tlas_topology(k, 2)
    node_lo, node_hi = tlas_node_bounds(
        topology, jnp.asarray(lo), jnp.asarray(hi)
    )
    node_lo, node_hi = np.asarray(node_lo), np.asarray(node_hi)
    for i in range(topology.skip.shape[0]):
        members = topology.member[i]
        np.testing.assert_array_equal(node_lo[i], lo[members].min(axis=0))
        np.testing.assert_array_equal(node_hi[i], hi[members].max(axis=0))


def test_instance_morton_order_is_permutation_and_stable():
    from tpu_render_cluster.render.mesh import instance_morton_order

    rng = np.random.default_rng(3)
    k = 48
    lo = rng.uniform(-6, 5, (k, 3)).astype(np.float32)
    hi = lo + 1.0
    order = np.asarray(instance_morton_order(jnp.asarray(lo), jnp.asarray(hi)))
    assert sorted(order.tolist()) == list(range(k))
    # Degenerate all-overlapping field: equal codes keep original order
    # (stable argsort), so the TLAS table equals the flat table.
    same = np.tile(lo[:1], (k, 1))
    order = np.asarray(
        instance_morton_order(jnp.asarray(same), jnp.asarray(same + 1.0))
    )
    np.testing.assert_array_equal(order, np.arange(k))


def test_use_tlas_for_resolution(monkeypatch):
    from tpu_render_cluster.render import pallas_kernels as pk

    monkeypatch.delenv("TRC_TLAS", raising=False)
    monkeypatch.delenv("TRC_TLAS_LEAF", raising=False)
    assert pk.tlas_enabled()  # default on
    assert pk.use_tlas_for(48, None)
    assert pk.use_tlas_for(48, False) is False
    # Fields that fit in one leaf degenerate to flat + a root test:
    # auto-disabled even when requested.
    assert pk.use_tlas_for(1, True) is False
    assert pk.use_tlas_for(4, True) is False
    monkeypatch.setenv("TRC_TLAS", "0")
    assert pk.use_tlas_for(48, None) is False
    assert pk.use_tlas_for(48, True)  # explicit request beats the env tier
    monkeypatch.setenv("TRC_TLAS", "1")
    monkeypatch.setenv("TRC_TLAS_LEAF", "16")
    assert pk.use_tlas_for(16, None) is False
    assert pk.use_tlas_for(17, None)


# -- kernel-level equivalence ------------------------------------------------


def _random_field(seed: int, k: int):
    """A randomized instance field over the deep scene's shared BLAS."""
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
        bvh=cached_mesh_bvh("icosphere"),
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
    """Degenerate all-overlapping field: K identical instances. Every
    TLAS node unions to the same box (no pruning possible) and every
    nearest walk ties exactly — identical instances make any tie-break
    shade identically, so TLAS-vs-flat must still match bitwise."""
    from tpu_render_cluster.render.mesh import (
        MeshInstances,
        MeshSet,
        cached_mesh_bvh,
    )

    return MeshSet(
        bvh=cached_mesh_bvh("icosphere"),
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
    """Random ray state aimed at the field (origins above, directions
    biased downward so walks hit instances AND fire NEE shadow rays)."""
    rng = np.random.default_rng(seed)
    origins = rng.uniform(-5, 5, (n, 3)).astype(np.float32)
    origins[:, 1] = rng.uniform(0.5, 6.0, n).astype(np.float32)
    directions = rng.normal(size=(n, 3)).astype(np.float32)
    directions[:, 1] -= 1.0
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    return jnp.asarray(origins), jnp.asarray(directions)


def _one_bounce(mesh, origins, directions, *, use_tlas, bounce=0):
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene(DEEP_SCENE, 5)
    n = origins.shape[0]
    throughput = jnp.ones((n, 3), jnp.float32)
    alive = jnp.ones((n,), bool)
    return pk.mesh_bounce_pallas(
        scene, mesh, origins, directions, throughput, alive,
        jnp.int32(1234), bounce, total_bounces=4,
        live_count=jnp.int32(n), use_tlas=use_tlas,
    )


@pytest.mark.parametrize(
    "field",
    ["random-12", "random-48", "overlapping-8", "single"],
)
def test_tlas_matches_flat_one_bounce(monkeypatch, field):
    """One fused bounce (nearest + NEE shadow any-hits + shading) on a
    randomized/degenerate field: TLAS and flat kernels must agree on
    every output — per-lane results are instance-order invariant, and
    the TLAS walk's per-node cull is conservative (a node containing a
    lane's true nearest hit can never be skipped for that lane)."""
    monkeypatch.setenv("TRC_PALLAS", "1")
    if field == "random-12":
        mesh = _random_field(11, 12)
    elif field == "random-48":
        mesh = _random_field(13, 48)
    elif field == "overlapping-8":
        mesh = _overlapping_field(8)
    else:
        mesh = _random_field(17, 1)  # auto-degrades to the flat sweep
    origins, directions = _bounce_state(29, 256)
    flat = _one_bounce(mesh, origins, directions, use_tlas=False)
    tlas = _one_bounce(mesh, origins, directions, use_tlas=True)
    labels = ("contribution", "origins", "directions", "throughput", "alive")
    for name, a, b in zip(labels, flat[:5], tlas[:5]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-6,
            err_msg=f"{field}: {name} diverges TLAS vs flat",
        )
    assert flat[5] is None  # flat kernels emit no key column
    if field == "single":
        assert tlas[5] is None  # 1-instance field degraded to flat
    else:
        assert tlas[5] is not None


def test_tlas_matches_flat_two_instance_leaf_one(monkeypatch):
    """Smallest REAL hierarchy: 2 instances, leaf size 1 (root + two
    leaves) — exercises inner-node descent and leaf windows without the
    auto-degrade masking the walk."""
    monkeypatch.setenv("TRC_PALLAS", "1")
    monkeypatch.setenv("TRC_TLAS_LEAF", "1")
    mesh = _random_field(19, 2)
    origins, directions = _bounce_state(31, 128)
    flat = _one_bounce(mesh, origins, directions, use_tlas=False)
    tlas = _one_bounce(mesh, origins, directions, use_tlas=True)
    for a, b in zip(flat[:5], tlas[:5]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-6
        )
    assert tlas[5] is not None


def test_kernel_key_epilogue_matches_xla_twin(monkeypatch):
    """The fused sort-key column equals mesh_sort_keys recomputed from
    the kernel's own post-bounce outputs — bit-for-bit on live lanes.
    This is the contract that lets bounce 0 derive keys in XLA while
    bounces 1+ read the kernel column: both sides share the ONE
    bit-packer (coherence_key_u32) and quantization window, and the
    candidate component shares its semantics (nearest-entry overlapped
    instance over the Morton-sorted slot table — the kernel's AABB-only
    TLAS walk and the XLA broadphase pick the same winner; strict-<
    improvement makes ties resolve to the lowest slot on both sides).
    Dead lanes may differ in candidate only: the kernel's walk never
    lets them drive a descent, so they can keep the sentinel where the
    XLA twin computes a stale candidate — their dead bit dominates the
    sort either way."""
    from tpu_render_cluster.render import pallas_kernels as pk
    from tpu_render_cluster.render.mesh import instance_morton_order

    monkeypatch.setenv("TRC_PALLAS", "1")
    mesh = _random_field(23, 12)
    origins, directions = _bounce_state(37, 256)
    _, o2, d2, _, alive2, keys = _one_bounce(
        mesh, origins, directions, use_tlas=True
    )
    table = pk._instance_table(
        mesh.instances.rotation, mesh.instances.translation,
        mesh.instances.scale, mesh.bvh.bounds_min, mesh.bvh.bounds_max,
    )
    lo_w, hi_w = table[:, 13:16], table[:, 16:19]
    order = instance_morton_order(lo_w, hi_w)
    key_lo, key_inv = pk.mesh_key_bounds(lo_w, hi_w)
    expected = pk.mesh_sort_keys(
        o2, d2, alive2, key_lo, key_inv,
        candidate=pk.instance_entry_candidates(
            o2, d2, lo_w[order], hi_w[order]
        ),
    )
    keys, expected = np.asarray(keys), np.asarray(expected)
    live = np.asarray(alive2)
    np.testing.assert_array_equal(keys[live], expected[live])
    # Dead lanes: everything but the candidate bits [18:24) matches.
    cand_mask = ~(0x3F << 18)
    np.testing.assert_array_equal(
        keys[~live] & cand_mask, expected[~live] & cand_mask
    )
    # Keys are always positive int32 (< 2^30), so a plain ascending
    # argsort orders them like the uint32 bit pattern would.
    assert (keys >= 0).all()
    # Dead lanes carry the dead bit: they sort after every live lane.
    if (~live).any() and live.any():
        assert keys[~live].min() > keys[live].max()


# -- image equivalence ----------------------------------------------


def _masked_uint8(scene_name, use_tlas, **kwargs):
    from tpu_render_cluster.render.integrator import fused_frame_renderer

    renderer = fused_frame_renderer(
        scene_name, kwargs["width"], kwargs["height"], kwargs["samples"],
        kwargs["max_bounces"], use_tlas,
    )
    return np.asarray(renderer(30))


@pytest.mark.parametrize("scene_name", [DEEP_SCENE, SHALLOW_SCENE])
def test_masked_image_tlas_vs_flat_uint8_identical(monkeypatch, scene_name):
    """Masked tier (deep per-bounce path for 03, fused megakernel for
    02): the tonemapped uint8 frame is identical TLAS vs flat. Both
    variants coexist in one process as distinct compiled programs — the
    property the interleaved A/B bench relies on."""
    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    kwargs = dict(width=12, height=12, samples=1, max_bounces=2)
    flat = _masked_uint8(scene_name, False, **kwargs)
    tlas = _masked_uint8(scene_name, True, **kwargs)
    np.testing.assert_array_equal(flat, tlas)


# -- compile/build bounds ----------------------------------------------------


def test_tlas_adds_no_per_frame_compiles_or_builds(monkeypatch):
    """Three frames through the TLAS kernels: the one program
    (render_compiles_total) and the one TLAS topology build are
    first-sighted on frame 1 — frames 2..3 add nothing. The topology is
    memoized per (instance count, leaf size); per-frame work is only
    the traced bounds refresh inside the already-compiled program."""
    from tpu_render_cluster.obs import render_compile_counter
    from tpu_render_cluster.render.integrator import fused_frame_renderer
    from tpu_render_cluster.render.mesh import tlas_build_counter

    monkeypatch.setenv("TRC_PALLAS", "1")
    fused_frame_renderer.cache_clear()
    counter = render_compile_counter()
    builds = tlas_build_counter()

    def render(frame):
        renderer = fused_frame_renderer(DEEP_SCENE, 8, 8, 1, 2, True, with_live=True)
        np.asarray(renderer(frame)[0])

    before, builds_before = counter.value(), builds.value()
    render(30)
    assert counter.value() == before + 1
    assert builds.value() == builds_before + 1
    for frame in (31, 32):
        render(frame)
    assert counter.value() == before + 1
    assert builds.value() == builds_before + 1
