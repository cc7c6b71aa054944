"""Procedural scenes as static-shape structure-of-arrays.

Each scene family mirrors one of the reference's Blender job projects
(reference: blender-projects/{01_simple-animation,02_physics,03_physics-2,
04_very-simple}) in spirit: a ground plane, a set of spheres, a sun light,
and a sky. Scene arrays are pure functions of the frame index (animation
and physics are closed-form in time), so a batch of frames can be built
with ``jax.vmap(lambda f: build_scene(name, f))`` and rendered as one
device-resident batch — no host round-trips between frames.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Scene(NamedTuple):
    """Structure-of-arrays scene with static shapes (pads with radius=0)."""

    centers: jnp.ndarray  # [N, 3] float32
    radii: jnp.ndarray  # [N] float32, 0 = unused slot
    albedo: jnp.ndarray  # [N, 3] float32
    emission: jnp.ndarray  # [N, 3] float32
    # Ground plane y=0 with a checkerboard albedo.
    plane_albedo_a: jnp.ndarray  # [3]
    plane_albedo_b: jnp.ndarray  # [3]
    # Sun (delta directional light).
    sun_direction: jnp.ndarray  # [3], unit, points TOWARD the sun
    sun_color: jnp.ndarray  # [3]
    # Sky gradient colors.
    sky_horizon: jnp.ndarray  # [3]
    sky_zenith: jnp.ndarray  # [3]


SCENE_NAMES = (
    "04_very-simple",
    "01_simple-animation",
    "02_physics-mesh",
    "02_physics",
    "03_physics-2-assets",
    "03_physics-2-scan",
    "03_physics-2-mesh",
    "03_physics-2",
)
# The rigid-body families whose 48 bodies share one set of transforms:
# icospheres, instances of the 871,200-triangle scan stand-in, or
# instances of three different scan stand-ins (body i is model i mod 3).
_PHYSICS_2_BODIES = (
    "03_physics-2-mesh", "03_physics-2-scan", "03_physics-2-assets",
)

_FPS = 24.0
_GRAVITY = 9.81


def _normalize(v):
    return v / jnp.linalg.norm(v)


def _default_lighting() -> dict:
    return dict(
        plane_albedo_a=jnp.array([0.85, 0.85, 0.85], jnp.float32),
        plane_albedo_b=jnp.array([0.25, 0.3, 0.35], jnp.float32),
        sun_direction=_normalize(jnp.array([0.4, 0.8, 0.3], jnp.float32)),
        sun_color=jnp.array([2.7, 2.5, 2.2], jnp.float32),
        sky_horizon=jnp.array([0.65, 0.75, 0.9], jnp.float32),
        sky_zenith=jnp.array([0.15, 0.3, 0.6], jnp.float32),
    )


def _pad_spheres(centers, radii, albedo, emission, size: int) -> tuple:
    n = centers.shape[0]
    if n > size:
        raise ValueError(f"Scene has {n} spheres, exceeds pad size {size}.")
    pad = size - n
    centers = jnp.concatenate([centers, jnp.zeros((pad, 3), jnp.float32)])
    radii = jnp.concatenate([radii, jnp.zeros((pad,), jnp.float32)])
    albedo = jnp.concatenate([albedo, jnp.zeros((pad, 3), jnp.float32)])
    emission = jnp.concatenate([emission, jnp.zeros((pad, 3), jnp.float32)])
    return centers, radii, albedo, emission


def _grid_colors(n: int) -> jnp.ndarray:
    """Deterministic pleasant albedos (golden-ratio hue walk)."""
    indices = jnp.arange(n, dtype=jnp.float32)
    hue = jnp.mod(indices * 0.61803398875, 1.0)
    # Cheap HSV->RGB with fixed s/v.
    h6 = hue * 6.0
    x = 1.0 - jnp.abs(jnp.mod(h6, 2.0) - 1.0)
    zeros = jnp.zeros_like(hue)
    ones = jnp.ones_like(hue)
    sector = jnp.floor(h6).astype(jnp.int32) % 6
    r = jnp.select([sector == 0, sector == 1, sector == 2, sector == 3, sector == 4], [ones, x, zeros, zeros, x], ones)
    g = jnp.select([sector == 0, sector == 1, sector == 2, sector == 3, sector == 4], [x, ones, ones, x, zeros], zeros)
    b = jnp.select([sector == 0, sector == 1, sector == 2, sector == 3, sector == 4], [zeros, zeros, x, ones, ones], x)
    rgb = jnp.stack([r, g, b], axis=-1)
    return 0.25 + 0.65 * rgb


def _very_simple(frame: jnp.ndarray, n_spheres: int = 64, pad: int = 64):
    """Static sphere grid (the 04_very-simple workhorse scene)."""
    side = int(np.ceil(np.sqrt(n_spheres)))
    index = jnp.arange(n_spheres)
    gx = (index % side).astype(jnp.float32) - (side - 1) / 2.0
    gz = (index // side).astype(jnp.float32) - (side - 1) / 2.0
    radius = jnp.full((n_spheres,), 0.45, jnp.float32)
    centers = jnp.stack([gx * 1.2, radius, gz * 1.2], axis=-1)
    albedo = _grid_colors(n_spheres)
    emission = jnp.zeros((n_spheres, 3), jnp.float32)
    # One emissive sphere so indirect light is visible.
    emission = emission.at[0].set(jnp.array([4.0, 3.6, 3.0]))
    return _pad_spheres(centers, radius, albedo, emission, pad)


def _simple_animation(frame: jnp.ndarray, n_spheres: int = 24, pad: int = 32):
    """Spheres orbiting a center column, phase-shifted per sphere."""
    t = frame / _FPS
    index = jnp.arange(n_spheres, dtype=jnp.float32)
    phase = index * (2.0 * jnp.pi / n_spheres)
    ring = 1.0 + (index % 3.0)
    angle = phase + t * (0.8 + 0.15 * (index % 3.0))
    y = 0.5 + 0.3 * jnp.sin(t * 2.0 + phase * 2.0) + 0.35 * (index % 3.0)
    centers = jnp.stack(
        [ring * 1.4 * jnp.cos(angle), y, ring * 1.4 * jnp.sin(angle)], axis=-1
    )
    radii = jnp.full((n_spheres,), 0.35, jnp.float32)
    albedo = _grid_colors(n_spheres)
    emission = jnp.zeros((n_spheres, 3), jnp.float32)
    emission = emission.at[0].set(jnp.array([5.0, 4.5, 3.5]))
    return _pad_spheres(centers, radii, albedo, emission, pad)


def _physics(frame: jnp.ndarray, n_spheres: int, pad: int, *, chaos: float):
    """Falling-and-bouncing spheres with closed-form ballistic motion.

    A cheap stand-in for the reference's baked rigid-body sims
    (blender-projects/02_physics, 03_physics-2): each sphere drops from a
    per-sphere height with elastic bounces (restitution 0.7), so position
    at any frame is computable without simulation state.
    """
    t = frame / _FPS
    index = jnp.arange(n_spheres, dtype=jnp.float32)
    # Deterministic pseudo-random spread from the index.
    u1 = jnp.mod(index * 0.7548776662, 1.0)
    u2 = jnp.mod(index * 0.5698402909, 1.0)
    u3 = jnp.mod(index * 0.3819660113, 1.0)
    radius = 0.25 + 0.15 * u3
    x = (u1 - 0.5) * 8.0 + chaos * 0.5 * jnp.sin(12.0 * u2)
    z = (u2 - 0.5) * 8.0 + chaos * 0.5 * jnp.cos(12.0 * u1)
    h0 = 3.0 + 5.0 * u3  # drop height
    drop_delay = u1 * 2.0 * chaos
    tau = jnp.maximum(t - drop_delay, 0.0)

    y = _ballistic_height(tau, h0) + radius
    centers = jnp.stack([x, y, z], axis=-1)
    albedo = _grid_colors(n_spheres)
    emission = jnp.zeros((n_spheres, 3), jnp.float32)
    return _pad_spheres(centers, radius, albedo, emission, pad)


def _ballistic_height(t, h0, *, restitution: float = 0.7):
    """Closed-form bounce height at time t for a drop from h0 (see _physics)."""
    e = restitution
    v0 = jnp.sqrt(2.0 * _GRAVITY * h0)
    t_fall = jnp.sqrt(2.0 * h0 / _GRAVITY)
    in_fall = t < t_fall
    fall_y = h0 - 0.5 * _GRAVITY * t**2
    s = t - t_fall
    denom = 2.0 * v0 / (_GRAVITY * (1.0 - e))
    ratio = jnp.clip(1.0 - s / denom, 1e-6, 1.0)
    k = jnp.clip(jnp.floor(jnp.log(ratio) / jnp.log(e)), 0.0, 40.0)
    elapsed = denom * (1.0 - e**k)
    local = s - elapsed
    vk = v0 * e**k
    bounce_y = jnp.maximum(vk * local - 0.5 * _GRAVITY * local**2, 0.0)
    settled = vk < 0.15
    return jnp.where(in_fall, fall_y, jnp.where(settled, 0.0, bounce_y))


def build_mesh_instances(name: str, frame):
    """Mesh instance transforms for mesh-backed scenes, else ``None``.

    02_physics-mesh: K tumbling boxes dropped ballistically (the mesh
    counterpart of the _physics sphere rain — reference analog:
    blender-projects/02_physics rigid bodies). Topology is static (one
    shared box BVH); only the rigid transforms depend on the frame, so the
    whole thing jits and vmaps over frames.
    """
    if name != "02_physics-mesh" and name not in _PHYSICS_2_BODIES:
        return None
    from tpu_render_cluster.render import mesh
    from tpu_render_cluster.render.mesh import MeshInstances, rotation_y

    frame = jnp.asarray(frame, jnp.float32)
    t = frame / _FPS
    # 03's variant: more, smaller icosphere instances (chaotic spread) —
    # the deeper 127-node BVH makes traversal depth matter.
    k = 48 if name in _PHYSICS_2_BODIES else 24
    index = jnp.arange(k, dtype=jnp.float32)
    u1 = jnp.mod(index * 0.7548776662, 1.0)
    u2 = jnp.mod(index * 0.5698402909, 1.0)
    u3 = jnp.mod(index * 0.3819660113, 1.0)
    if name in _PHYSICS_2_BODIES:
        size = 0.45 + 0.35 * u3
        x = (u1 - 0.5) * 9.0 + 0.5 * jnp.sin(12.0 * u2)
        z = (u2 - 0.5) * 9.0 + 0.5 * jnp.cos(12.0 * u1)
        h0 = 2.0 + 5.0 * u3
        tau = jnp.maximum(t - u1 * 2.0, 0.0)
    else:
        size = 0.6 + 0.5 * u3
        x = (u1 - 0.5) * 7.0
        z = (u2 - 0.5) * 7.0
        h0 = 2.5 + 4.0 * u3
        tau = jnp.maximum(t - u1 * 1.5, 0.0)
    y = _ballistic_height(tau, h0) + size * 0.5
    rotation = rotation_y(tau * (0.6 + 2.0 * u2) + u1 * 6.28)
    translation = jnp.stack([x, y, z], axis=-1)
    albedo = _grid_colors(k)
    # Which BLAS of the scene's set a body is: a rule of the family, never
    # of the frame (NumPy, so it is a constant of any trace it meets).
    models = len(mesh.ASSET_MODELS) if name == "03_physics-2-assets" else 1
    return MeshInstances(
        rotation=rotation, translation=translation, albedo=albedo, scale=size,
        model=(np.arange(k) % models).astype(np.int32),
    )


def obj_stage_scene(frame) -> Scene:
    """Minimal stage for user OBJ meshes (``render.cli --obj``): two accent
    spheres beside the turntable, default plane/sun/sky."""
    del frame  # static stage; the OBJ instance itself rotates per frame
    centers = jnp.array(
        [[2.6, 0.45, -1.4], [-2.4, 0.35, 1.6]], jnp.float32
    )
    radii = jnp.array([0.45, 0.35], jnp.float32)
    albedo = jnp.array([[0.8, 0.35, 0.3], [0.3, 0.45, 0.8]], jnp.float32)
    emission = jnp.zeros((2, 3), jnp.float32)
    padded = _pad_spheres(centers, radii, albedo, emission, 8)
    return Scene(*padded, **_default_lighting())


def mesh_kind_for_scene(name: str) -> str | None:
    """Which cached object-space BVH a mesh scene uses (None = no mesh)."""
    if name == "02_physics-mesh":
        return "box"
    if name == "03_physics-2-mesh":
        return "icosphere"
    if name == "03_physics-2-scan":
        return "scan"
    if name == "03_physics-2-assets":
        return "assets"  # a set: mesh.ASSET_MODELS' three BLASes
    return None


def build_scene(name: str, frame) -> Scene:
    """Build the scene arrays for one frame (jit/vmap friendly in ``frame``)."""
    frame = jnp.asarray(frame, jnp.float32)
    if name == "04_very-simple":
        spheres = _very_simple(frame)
    elif name == "01_simple-animation":
        spheres = _simple_animation(frame)
    elif name == "02_physics":
        spheres = _physics(frame, 48, 64, chaos=0.0)
    elif name == "02_physics-mesh":
        # A handful of spheres accompany the boxes (sky + plane + spheres
        # exercise every primitive in one scene); the boxes ride the mesh
        # path via build_mesh_instances.
        spheres = _physics(frame, 12, 16, chaos=0.0)
    elif name == "03_physics-2":
        spheres = _physics(frame, 96, 128, chaos=1.0)
    elif name in _PHYSICS_2_BODIES:
        spheres = _physics(frame, 16, 16, chaos=1.0)
    else:
        raise ValueError(f"Unknown scene: {name!r} (have {SCENE_NAMES})")
    centers, radii, albedo, emission = spheres
    return Scene(centers, radii, albedo, emission, **_default_lighting())


def scene_for_job_name(job_name: str) -> str:
    """Map a job name to a scene family.

    Covers the reference TOML convention ("01-simple-animation_...",
    "04_very-simple_...") and this repo's generated grid labels
    ("01sa_...", "02ph_...", "03ph2_...", "04vs_..."): the two-digit
    project number prefix is unique across families.
    """
    # Exact family-name prefixes first, longest first, so
    # "02_physics-mesh_x" doesn't fall through to "02_physics".
    for name in sorted(SCENE_NAMES, key=len, reverse=True):
        if job_name.startswith(name):
            return name
    # Two-digit project prefixes map to the classic (non-mesh) families.
    for name in SCENE_NAMES:
        if name.endswith(("-mesh", "-scan", "-assets")):
            continue
        if job_name.startswith(name.split("_", 1)[0]):
            return name
    return "04_very-simple"
