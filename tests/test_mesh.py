"""Triangle-mesh + BVH tests (SURVEY.md §7 hard part #4).

The XLA threaded-BVH packet walk is verified against the brute-force
Möller–Trumbore reference on the same inputs, and the Pallas bounce kernel
(``mesh_bounce_pallas``, through ``_trace_paths_deep``) against the XLA
bounce loop at one bounce, where the radiance is RNG-free.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TRC_PALLAS", "0")

import jax.numpy as jnp  # noqa: E402

from tpu_render_cluster.render import mesh as mesh_mod  # noqa: E402
from tpu_render_cluster.render.mesh import (  # noqa: E402
    MeshInstances,
    build_bvh,
    cached_mesh_bvh,
    intersect_bvh_packet,
    intersect_instances,
    intersect_triangles_brute,
    make_box,
    make_icosphere,
    rotation_y,
)


def _rays(n: int, seed: int = 0, spread: float = 0.3):
    rng = np.random.default_rng(seed)
    origins = rng.normal(size=(n, 3)).astype(np.float32) * spread
    origins[:, 2] -= 3.0
    directions = np.array([0.0, 0.0, 1.0], np.float32) + rng.normal(
        size=(n, 3)
    ).astype(np.float32) * spread
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    return jnp.asarray(origins), jnp.asarray(directions.astype(np.float32))


@pytest.mark.parametrize("kind", ["box", "icosphere"])
def test_bvh_packet_matches_brute_force(kind):
    bvh = cached_mesh_bvh(kind)
    origins, directions = _rays(512)
    t_brute, idx_brute = intersect_triangles_brute(bvh, origins, directions)
    t_packet, idx_packet = intersect_bvh_packet(bvh, origins, directions)
    np.testing.assert_allclose(
        np.asarray(t_packet), np.asarray(t_brute), rtol=1e-5, atol=1e-5
    )
    hit = np.asarray(t_brute) < 1e29
    assert hit.sum() > 20, "test rays must actually hit the mesh"
    assert (np.asarray(idx_packet)[hit] == np.asarray(idx_brute)[hit]).all()


def test_bvh_structure_invariants():
    vertices, faces = make_icosphere(2)
    bvh = build_bvh(vertices, faces)
    n_nodes = bvh.skip.shape[0]
    skip = np.asarray(bvh.skip)
    count = np.asarray(bvh.count)
    first = np.asarray(bvh.first)
    # Skip links always advance and never overshoot.
    assert (skip > np.arange(n_nodes)).all()
    assert (skip <= n_nodes).all()
    # Leaves are LEAF_SIZE-aligned slots within the padded triangle array.
    leaves = count > 0
    assert (first[leaves] % mesh_mod.LEAF_SIZE == 0).all()
    assert (count[leaves] <= mesh_mod.LEAF_SIZE).all()
    assert bvh.v0.shape[0] % mesh_mod.LEAF_SIZE == 0
    # Every real triangle is referenced by exactly one leaf slot.
    assert int(count.sum()) == len(faces)


def test_instance_transform_preserves_t():
    # A scaled/rotated/translated instance must report hit distances in
    # world units: a unit box at distance 5 scaled by s is hit at
    # t = 5 - s/2 by a centered axis ray.
    bvh = cached_mesh_bvh("box")
    for scale in (0.5, 1.0, 2.0):
        instances = MeshInstances(
            rotation=rotation_y(jnp.zeros((1,)))
            .reshape(1, 3, 3)
            .astype(jnp.float32),
            translation=jnp.array([[0.0, 0.0, 5.0]], jnp.float32),
            albedo=jnp.ones((1, 3), jnp.float32),
            scale=jnp.array([scale], jnp.float32),
        )
        origins = jnp.zeros((4, 3), jnp.float32)
        directions = jnp.tile(
            jnp.array([[0.0, 0.0, 1.0]], jnp.float32), (4, 1)
        )
        t, normal, albedo = intersect_instances(
            bvh, instances, origins, directions
        )
        np.testing.assert_allclose(
            np.asarray(t), 5.0 - scale / 2.0, rtol=1e-5
        )
        # Front face normal flipped toward the ray.
        np.testing.assert_allclose(
            np.asarray(normal)[0], [0.0, 0.0, -1.0], atol=1e-5
        )


@pytest.mark.parametrize(
    "scene", ["02_physics-mesh", "03_physics-2-mesh"]
)
def test_mesh_scene_renders(scene):
    from tpu_render_cluster.render.integrator import render_frame

    image = np.asarray(
        render_frame(scene, 30, width=64, height=64, samples=2, max_bounces=2)
    )
    assert image.shape == (64, 64, 3)
    assert image.std() > 0.05, "mesh scene must have non-trivial content"
    assert np.isfinite(image).all()


def test_mesh_scene_job_name_mapping():
    from tpu_render_cluster.render.scene import scene_for_job_name

    assert scene_for_job_name("02_physics-mesh_240f") == "02_physics-mesh"
    assert scene_for_job_name("03_physics-2-mesh_240f") == "03_physics-2-mesh"
    assert scene_for_job_name("03-physics-2_measuring") == "03_physics-2"
    assert scene_for_job_name("02_physics_demo") == "02_physics"
    assert scene_for_job_name("04_very-simple_10f") == "04_very-simple"


def test_occlusion_anyhit_matches_nearest_hit():
    # The dedicated any-hit walk must agree with "nearest hit exists" from
    # the brute-force reference, and respect the `already` mask (the
    # bounce kernel's half: test_bounce_kernel_shadow_walk_keeps_already).
    import jax.numpy as jnp

    from tpu_render_cluster.render.mesh import occluded_bvh_packet

    bvh = cached_mesh_bvh("icosphere")
    origins, directions = _rays(300, seed=5)
    t_brute, _ = intersect_triangles_brute(bvh, origins, directions)
    expected = np.asarray(t_brute) < 1e29
    none = jnp.zeros((300,), bool)
    occ_xla = np.asarray(occluded_bvh_packet(bvh, origins, directions, none))
    assert (occ_xla == expected).all()
    # already-occluded rays stay occluded.
    all_occ = jnp.ones((300,), bool)
    assert np.asarray(
        occluded_bvh_packet(bvh, origins, directions, all_occ)
    ).all()


# ---------------------------------------------------------------------------
# The Pallas bounce kernel against the XLA bounce loop, one bounce


def _one_instance(kind):
    """The mesh alone at the origin, unrotated and unscaled."""
    return mesh_mod.MeshSet(
        cached_mesh_bvh(kind),
        MeshInstances(
            rotation=jnp.eye(3, dtype=jnp.float32)[None],
            translation=jnp.zeros((1, 3), jnp.float32),
            albedo=jnp.asarray([[0.8, 0.5, 0.3]], jnp.float32),
            scale=jnp.ones((1,), jnp.float32),
        ),
    )


def _five_boxes():
    """Five boxes of distinct rotations, scales and albedos: more than one
    TLAS leaf of 4, so the two-level variant engages."""
    rng = np.random.default_rng(11)
    k = 5
    angles = jnp.asarray(rng.uniform(0, 2 * np.pi, size=k).astype(np.float32))
    return mesh_mod.MeshSet(
        cached_mesh_bvh("box"),
        MeshInstances(
            rotation=rotation_y(angles).astype(jnp.float32),
            translation=jnp.asarray(
                rng.uniform(-2, 2, size=(k, 3)).astype(np.float32)
            ),
            albedo=jnp.asarray(
                rng.uniform(0.2, 1.0, size=(k, 3)).astype(np.float32)
            ),
            scale=jnp.asarray(rng.uniform(0.5, 1.5, size=k).astype(np.float32)),
        ),
    )


@pytest.mark.parametrize(
    "mesh_set, rays, use_tlas",
    [
        (lambda: _one_instance("box"), dict(n=300, seed=2), None),
        (lambda: _one_instance("icosphere"), dict(n=300, seed=2), None),
        (_five_boxes, dict(n=400, seed=7, spread=0.8), False),
        (_five_boxes, dict(n=400, seed=7, spread=0.8), True),
    ],
    ids=["box", "icosphere", "five_boxes_flat", "five_boxes_tlas"],
)
def test_bounce_kernel_matches_xla_loop(monkeypatch, mesh_set, rays, use_tlas):
    """``mesh_bounce_pallas`` through ``_trace_paths_deep`` (the re-sort,
    the launch and the unsort) against one bounce of the XLA loop: sky,
    the mesh's, spheres' and plane's nearest hit, the instance's albedo
    and the triangle's normal through the sun term, both shadow walks. The
    resampled direction is never traced, so no lane depends on an RNG."""
    import jax

    from tpu_render_cluster.render import integrator, pallas_kernels
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene("02_physics-mesh", 1)
    mesh = mesh_set()
    origins, directions = _rays(**rays)
    assert not pallas_kernels.pallas_enabled()  # the XLA loop below
    want = np.asarray(
        integrator.trace_paths(
            scene, origins, directions, jax.random.PRNGKey(3),
            max_bounces=1, mesh=mesh,
        )
    )
    t_mesh, _, _ = intersect_instances(
        mesh.bvh, mesh.instances, origins, directions
    )
    assert (np.asarray(t_mesh) < 1e29).sum() > 20, "rays must hit the mesh"
    if use_tlas is not None:
        k = mesh.instances.translation.shape[0]
        assert pallas_kernels.use_tlas_for(k, use_tlas) == use_tlas
    got = np.asarray(
        integrator._trace_paths_deep(
            scene, mesh, origins, directions, jnp.int32(3), max_bounces=1,
            rng_lanes=None, use_tlas=use_tlas, quant=0, live_counts=None,
        )
    )
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_bounce_kernel_shadow_walk_keeps_already():
    """The bounce kernel's shadow walk: a lane a sphere already shadows
    comes back shadowed though its shadow ray meets no triangle, a lane
    whose shadow ray meets the mesh gets no sun, a lane neither shadows
    gets it. Each lane lands on the plane, whose only light at one bounce
    is the sun's."""
    import jax

    from tpu_render_cluster.render import integrator, pallas_kernels
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene("04_very-simple", 1)
    sun = np.asarray(scene.sun_direction, np.float64)
    assert sun[1] > 0.1
    sphere = np.array([-3.0, 3.0, 0.5])
    box = np.array([4.0, 3.0, 0.5])
    scene = scene._replace(
        centers=jnp.asarray([sphere], jnp.float32),
        radii=jnp.asarray([0.5], jnp.float32),
        albedo=jnp.full((1, 3), 0.5, jnp.float32),
        emission=jnp.zeros((1, 3), jnp.float32),
    )
    mesh = _one_instance("box")
    mesh = mesh._replace(
        instances=mesh.instances._replace(
            translation=jnp.asarray([box], jnp.float32)
        )
    )

    def shadow_of(centre):  # where the plane lies in its shadow
        return centre - sun * (centre[1] / sun[1])

    n = 128  # lanes 0: the sphere's shadow, 1: the box's, 2: open ground
    landing = np.tile(np.array([[0.3, 0.0, -7.3]]), (n, 1))
    landing[0], landing[1] = shadow_of(sphere), shadow_of(box)
    origins = jnp.asarray(landing + [0.0, 0.5, 0.0], jnp.float32)
    directions = jnp.tile(jnp.asarray([[0.0, -1.0, 0.0]], jnp.float32), (n, 1))
    contribution = np.asarray(
        pallas_kernels.mesh_bounce_pallas(
            scene, mesh, origins, directions, jnp.ones((n, 3), jnp.float32),
            jnp.ones((n,), bool), jnp.int32(3), jnp.int32(0), total_bounces=1,
        )[0]
    )
    assert not contribution[0].any()  # shadowed by the sphere: stays so
    assert not contribution[1].any()  # its shadow ray meets the box
    assert (contribution[2:] > 0.05).all()  # sunlit ground
    assert not pallas_kernels.pallas_enabled()
    want = np.asarray(
        integrator.trace_paths(
            scene, origins, directions, jax.random.PRNGKey(3),
            max_bounces=1, mesh=mesh,
        )
    )
    np.testing.assert_allclose(contribution, want, rtol=2e-3, atol=2e-3)
