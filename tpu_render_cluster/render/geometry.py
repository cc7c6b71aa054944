"""Ray-scene intersection.

The hot op is a [rays x spheres] batch intersection whose inner products are
matmul-shaped (``o @ centers^T``, ``d @ centers^T``) so XLA tiles them onto
the MXU. Padded sphere slots carry radius 0 and never produce hits. This is
the reference path: what ``integrator.trace_paths`` runs where the Pallas
kernels are off, and what every kernel in pallas_kernels.py is tested
against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_render_cluster.render.scene import Scene

# Plain Python float (not a jnp scalar): a module-level device constant
# would be created during whatever trace first imports the module and leak
# that trace's tracer into every later caller.
INF = 1e30
EPS = 1e-3


def _ray_barrier(origins, directions):
    """TPU-only fusion barrier around the ray inputs.

    Keeps XLA from fusing ray-producing broadcasts/iotas into the matmuls
    below: the v5e TpuPriorityFusionQueue cost model SIGILLs on that
    producer pattern (libtpu crash observed 2026-07; also materializes the
    rays once instead of recomputing them in all three contractions). On
    non-TPU backends the barrier buys nothing, so it is skipped.
    """
    if jax.default_backend() != "tpu":
        return origins, directions
    return jax.lax.optimization_barrier((origins, directions))


def intersect_spheres(scene: Scene, origins, directions):
    """Nearest sphere hit per ray, as an XLA pass (the reference path:
    what ``trace_paths`` runs where the Pallas kernels are off, and what
    every kernel is tested against).

    Args:
      origins, directions: [R, 3] float32 (directions unit).
    Returns:
      (t [R], index [R] int32) — t = INF when no hit.
    """
    origins, directions = _ray_barrier(origins, directions)
    oc_dot_d = directions @ scene.centers.T - jnp.sum(
        directions * origins, axis=-1, keepdims=True
    )  # [R, N] = d . (c - o)
    # |o - c|^2 = |o|^2 - 2 o.c + |c|^2
    o_sq = jnp.sum(origins * origins, axis=-1, keepdims=True)
    c_sq = jnp.sum(scene.centers * scene.centers, axis=-1)[None, :]
    oc_sq = o_sq - 2.0 * (origins @ scene.centers.T) + c_sq
    disc = oc_dot_d**2 - (oc_sq - scene.radii[None, :] ** 2)
    valid = (disc > 0.0) & (scene.radii[None, :] > 0.0)
    sqrt_disc = jnp.sqrt(jnp.maximum(disc, 0.0))
    t0 = oc_dot_d - sqrt_disc
    t1 = oc_dot_d + sqrt_disc
    t = jnp.where(t0 > EPS, t0, jnp.where(t1 > EPS, t1, INF))
    t = jnp.where(valid, t, INF)
    best = jnp.argmin(t, axis=-1).astype(jnp.int32)
    t_best = jnp.take_along_axis(t, best[:, None], axis=-1)[:, 0]
    return t_best, best


def intersect_plane(origins, directions):
    """Ground plane y=0; returns t (INF when parallel or behind)."""
    denom = directions[:, 1]
    t = -origins[:, 1] / jnp.where(jnp.abs(denom) < 1e-8, 1e-8, denom)
    return jnp.where((t > EPS) & (jnp.abs(denom) >= 1e-8), t, INF)


def intersect_scene(scene: Scene, origins, directions):
    """Nearest hit among spheres and the ground plane.

    Returns (t [R], sphere_index [R], is_plane [R] bool).
    """
    t_sphere, sphere_index = intersect_spheres(scene, origins, directions)
    t_plane = intersect_plane(origins, directions)
    is_plane = t_plane < t_sphere
    t = jnp.minimum(t_sphere, t_plane)
    return t, sphere_index, is_plane


def occluded(scene: Scene, origins, directions, max_t) -> jnp.ndarray:
    """Boolean shadow query: any sphere hit with t < max_t (plane excluded —
    the sun is always above the plane)."""
    t_sphere, _ = intersect_spheres(scene, origins, directions)
    return t_sphere < max_t


def occluded_sun(scene: Scene, origins, directions) -> jnp.ndarray:
    """Unbounded any-hit shadow query (the sun is a delta light at infinity).

    Cheaper than ``occluded``: no nearest-hit ordering or argmin is needed,
    just "does any sphere lie in front". An XLA pass like
    ``intersect_spheres``: the reference path.
    """
    origins, directions = _ray_barrier(origins, directions)
    oc_dot_d = directions @ scene.centers.T - jnp.sum(
        directions * origins, axis=-1, keepdims=True
    )
    o_sq = jnp.sum(origins * origins, axis=-1, keepdims=True)
    c_sq = jnp.sum(scene.centers * scene.centers, axis=-1)[None, :]
    oc_sq = o_sq - 2.0 * (origins @ scene.centers.T) + c_sq
    disc = oc_dot_d**2 - (oc_sq - scene.radii[None, :] ** 2)
    valid = (disc > 0.0) & (scene.radii[None, :] > 0.0)
    t1 = oc_dot_d + jnp.sqrt(jnp.maximum(disc, 0.0))
    return jnp.any(valid & (t1 > EPS), axis=-1)


def checker_albedo(scene: Scene, points) -> jnp.ndarray:
    """Checkerboard albedo for plane hit points [R, 3]."""
    checker = (
        jnp.floor(points[:, 0]).astype(jnp.int32)
        + jnp.floor(points[:, 2]).astype(jnp.int32)
    ) % 2
    return jnp.where(
        checker[:, None] == 0, scene.plane_albedo_a[None, :], scene.plane_albedo_b[None, :]
    )


def sky_color(scene: Scene, directions) -> jnp.ndarray:
    """Vertical-gradient sky with a visible sun disc."""
    blend = jnp.clip(directions[:, 1], 0.0, 1.0)[:, None]
    base = (1.0 - blend) * scene.sky_horizon[None, :] + blend * scene.sky_zenith[None, :]
    sun_cos = directions @ scene.sun_direction
    sun_disc = jnp.where(sun_cos > 0.9995, 40.0, 0.0)[:, None]
    return base + sun_disc * scene.sun_color[None, :] / 40.0 * 8.0
