"""Mesh megakernel equivalence tests.

Same acceptance pattern as tests/test_pallas_kernels.py for the sphere
megakernel: the fused whole-bounce-loop kernel for mesh scenes
(pallas_kernels.trace_paths_fused_mesh) must compute the same physics as
the XLA bounce loop, the reference. Single-bounce renders are RNG-free
(the resampled directions are never traced), so they must match
numerically; multi-bounce renders use different RNG streams and must
agree statistically.

Interpret mode on CPU is slow, so shapes are tiny.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TRC_PALLAS", "0")

import jax  # noqa: E402

SCENES = ["02_physics-mesh", "03_physics-2-mesh"]


def _render_both_paths(monkeypatch, scene, **kwargs):
    from tpu_render_cluster.render.integrator import render_frame

    monkeypatch.setenv("TRC_PALLAS", "0")
    jax.clear_caches()
    ref = np.asarray(render_frame(scene, 30, **kwargs))
    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    out = np.asarray(render_frame(scene, 30, **kwargs))
    jax.clear_caches()
    return out, ref


@pytest.mark.parametrize("scene", SCENES)
def test_deterministic_mesh_render_matches_reference_path(monkeypatch, scene):
    """Single-bounce mesh renders must agree across paths.

    With max_bounces=1 the radiance is sky + sun NEE of the primary hit
    only — sphere, plane, AND mesh intersections plus both shadow any-hit
    walks — computed by the megakernel in one launch (02_physics-mesh) or
    by the bounce kernel (03_physics-2-mesh) vs the XLA loop. Any mismatch
    is a physics bug, not noise.
    """
    out, ref = _render_both_paths(
        monkeypatch, scene, width=24, height=24, samples=2, max_bounces=1
    )
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def _deepest_eligible_mesh(scene_name="03_physics-2-mesh", frame=30):
    """03_physics-2-mesh's icosphere BLAS under as many of its instances
    as ``mesh_megakernel_eligible`` lets through: the deepest walk the
    served path can hand the megakernel."""
    from tpu_render_cluster.render import pallas_kernels
    from tpu_render_cluster.render.mesh import scene_mesh_set

    mesh = scene_mesh_set(scene_name, frame)
    n_nodes = mesh.bvh.skip.shape[0]
    k = pallas_kernels.MESH_MEGAKERNEL_MAX_WALK // n_nodes
    assert 1 < k < mesh.instances.translation.shape[0]
    instances = mesh.instances
    cut = mesh._replace(
        instances=instances._replace(
            rotation=instances.rotation[:k],
            translation=instances.translation[:k],
            albedo=instances.albedo[:k],
            scale=instances.scale[:k],
            model=None if instances.model is None else instances.model[:k],
        )
    )
    assert pallas_kernels.mesh_megakernel_eligible(cut)
    assert not pallas_kernels.mesh_megakernel_eligible(mesh)
    return mesh, cut


def test_megakernel_refuses_a_mesh_past_its_gate():
    """The gate is the kernel's precondition: whoever calls it past
    ``mesh_megakernel_eligible`` is told, where no test holds the walk to
    the reference."""
    import jax.numpy as jnp

    from tpu_render_cluster.render.pallas_kernels import trace_paths_fused_mesh
    from tpu_render_cluster.render.scene import build_scene

    whole, _ = _deepest_eligible_mesh()
    rays = jnp.zeros((8, 3), jnp.float32)
    with pytest.raises(ValueError, match="mesh_megakernel_eligible"):
        trace_paths_fused_mesh(
            build_scene("03_physics-2-mesh", 30), whole, rays, rays, 3,
            max_bounces=1,
        )


def test_megakernel_deep_tree_matches_xla(monkeypatch):
    """The megakernel's in-kernel walk on the DEEPEST tree it is served.

    02_physics-mesh (a 3-node box BLAS) is the one family the gate lets
    through, so the render_frame test above walks no tree of any depth.
    Here the kernel takes 03_physics-2-mesh's icosphere BLAS under as many
    instances as the gate admits (nodes x instances just under
    ``MESH_MEGAKERNEL_MAX_WALK``), on primary camera rays, and is pinned
    to the XLA reference at one bounce.
    """
    import jax.numpy as jnp

    from tpu_render_cluster.render.camera import camera_rays, scene_camera
    from tpu_render_cluster.render.integrator import trace_paths
    from tpu_render_cluster.render.pallas_kernels import trace_paths_fused_mesh
    from tpu_render_cluster.render.scene import build_scene

    scene_name = "03_physics-2-mesh"
    monkeypatch.setenv("TRC_PALLAS", "0")
    jax.clear_caches()
    scene = build_scene(scene_name, 30)
    _, mesh = _deepest_eligible_mesh(scene_name, 30)
    camera = scene_camera(scene_name, 30)
    side = 16
    # Off the pixel's centre: this camera's centred rays put two lanes
    # (row 5, columns 0 and 15) on the ground at z = -2.0 and x = -2.0 to
    # the last bit, on the line between two checker squares, where
    # floor() of a point one ulp either side legitimately picks either
    # albedo. That pair was this test's "2/256 lanes diverge" in every run
    # since the seed, with no mesh in the scene as with 48 instances: a
    # tie of the plane's, nothing of the walk's.
    origins, directions = camera_rays(
        camera, side, side, y0=0, x0=0, tile_height=side, tile_width=side,
        jitter=jnp.tile(jnp.asarray([[0.37, 0.61]]), (side * side, 1)),
    )
    ref = np.asarray(
        trace_paths(
            scene, origins, directions, jax.random.PRNGKey(3),
            max_bounces=1, mesh=mesh,
        )
    )
    out = np.asarray(
        trace_paths_fused_mesh(
            scene, mesh, origins, directions, 3, max_bounces=1
        )
    )
    jax.clear_caches()
    # Edge-tie lanes: a ray hitting exactly the shared edge of two
    # triangles legitimately resolves to either face's normal, and the two
    # implementations' borderline FP decisions (different reduction orders,
    # different det epsilons) can pick different-but-valid winners; which
    # lanes land on edges shifts with leaf grouping (LEAF_SIZE). The
    # budget is deliberately tight — 0.1% of lanes beyond the 2e-3
    # radiance tolerance, floored at one absolute lane (0.1% of these 256
    # lanes rounds to zero, and a single legitimate edge tie shifting with
    # platform/FP details must not fail the suite) — because the per-lane
    # culling machinery (seed-t, candidate-first sweep, scalar-branch leaf
    # skip) fails precisely as ISOLATED wrong lanes, not flipped regions;
    # a loose fraction would let a scattered-lane culling bug ship. The
    # mean absolute error bound catches the complementary failure: many
    # lanes each off by slightly more than noise.
    lane_diff = np.abs(out - ref).max(axis=1)
    n_diverged = int((lane_diff > 2e-3).sum())
    budget = max(1, round(0.001 * lane_diff.size))
    assert n_diverged <= budget, (
        f"{n_diverged}/{lane_diff.size} lanes diverge (budget {budget})"
    )
    mean_abs_error = float(np.abs(out - ref).mean())
    assert mean_abs_error < 1e-4, f"mean |out - ref| = {mean_abs_error:.2e}"


def test_stochastic_mesh_render_agrees_statistically(monkeypatch):
    """Multi-bounce renders from the two RNG streams converge together."""
    out, ref = _render_both_paths(
        monkeypatch,
        "02_physics-mesh",
        width=12,
        height=12,
        samples=64,
        max_bounces=2,
    )
    np.testing.assert_allclose(out.mean(), ref.mean(), rtol=0.02)
    np.testing.assert_allclose(
        out.mean(axis=(0, 1)), ref.mean(axis=(0, 1)), rtol=0.04
    )
    # Per-pixel bound scales with MC noise: the sphere test's 0.2 bound is
    # at 256 spp; at 64 spp (interpret-mode runtime budget) the estimator
    # sigma is 2x, so the few-sigma bound is ~0.45. Physics divergence is
    # caught by the mean assertions above and the deterministic tests.
    assert np.abs(out - ref).max() < 0.45, (
        f"max per-pixel diff {np.abs(out - ref).max():.3f}"
    )
