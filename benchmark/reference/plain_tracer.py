"""A plain path tracer of the scenes' semantics, in NumPy float32.

Independent of the code under test: written from the description of one
bounce (`render/integrator._shade_bounce`), with its own random numbers,
no kernels, no batching tricks. It takes the scene and the camera as
plain arrays. It is the reference the served images are held to where
the same-stream check cannot tell a wrong kernel from a right one (both
sides of that check run the same kernel).

One path, per bounce, at most `max_bounces` times:

1. nearest hit among the spheres (radius 0 = unused slot), the ground
   plane y = 0 and, in a mesh scene, the triangles of every instance
   (x_world = scale x rotation x x_obj + translation), ignoring hits
   nearer than EPS;
2. no hit: add throughput x sky (vertical gradient horizon->zenith by the
   direction's clamped y, plus a sun disc of sun_color x 8 where the
   direction is within cos > 0.9995 of the sun) and end the path;
3. hit: add throughput x emission (spheres only); a triangle's normal
   is its plane's, turned to face the ray, its albedo the instance's;
4. sun next-event: one shadow ray from the hit point (offset 4 EPS along
   the normal) towards the sun, blocked by any sphere or triangle;
   unblocked, add throughput x albedo x sun_color x max(n . sun, 0) / pi;
5. throughput *= albedo; the next direction is cosine-distributed about
   the normal (so BRDF x cos / pdf = albedo).

Triangles are tested one by one (Moeller-Trumbore), with no tree: an
instance is skipped only for rays that miss the sphere around it.

The plane's albedo is a unit checkerboard of two colours. A pixel is the
mean of `samples` paths through uniformly jittered positions inside it;
the display value is Reinhard (x / (1 + x)) then gamma 2.2, in [0, 255].
"""

from __future__ import annotations

import numpy as np

EPS = np.float32(1e-3)
INF = np.float32(1e30)
F = np.float32


def _normalize(v):
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def _hit_spheres(scene, origins, directions):
    """Nearest sphere per ray: (t, index); t = INF for none."""
    oc = scene["centers"][None, :, :] - origins[:, None, :]  # [R, N, 3]
    b = np.einsum("rnk,rk->rn", oc, directions)
    c = np.einsum("rnk,rnk->rn", oc, oc) - scene["radii"][None, :] ** 2
    disc = b * b - c
    valid = (disc > 0) & (scene["radii"][None, :] > 0)
    root = np.sqrt(np.maximum(disc, 0))
    near, far = b - root, b + root
    t = np.where(near > EPS, near, np.where(far > EPS, far, INF))
    t = np.where(valid, t, INF).astype(F)
    index = np.argmin(t, axis=1)
    return t[np.arange(len(t)), index], index


def _any_sphere_towards(scene, origins, directions):
    oc = scene["centers"][None, :, :] - origins[:, None, :]
    b = np.einsum("rnk,rk->rn", oc, directions)
    c = np.einsum("rnk,rnk->rn", oc, oc) - scene["radii"][None, :] ** 2
    disc = b * b - c
    valid = (disc > 0) & (scene["radii"][None, :] > 0)
    return np.any(valid & (b + np.sqrt(np.maximum(disc, 0)) > EPS), axis=1)


def world_triangles(mesh: dict) -> dict:
    """Every instance's triangles in world space, [K, T, 3] each, with the
    sphere that holds the instance and its albedo."""
    rotation, scale = mesh["rotation"], mesh["scale"][:, None, None]

    def turned(vectors):
        return (np.einsum("kij,tj->kti", rotation, vectors) * scale).astype(F)

    corners = np.stack([mesh["v0"], mesh["v0"] + mesh["e1"], mesh["v0"] + mesh["e2"]])
    reach = np.linalg.norm(corners, axis=-1).max()
    return {
        "v0": turned(mesh["v0"]) + mesh["translation"][:, None, :],
        "e1": turned(mesh["e1"]), "e2": turned(mesh["e2"]),
        "centre": mesh["translation"], "radius": mesh["scale"] * reach * F(1.001) + EPS,
        "albedo": mesh["albedo"],
    }


def _triangle_distances(origins, directions, v0, e1, e2):
    """[R, T] hit distances of rays against triangles (INF = miss)."""
    pvec = np.cross(directions[:, None, :], e2[None, :, :])
    det = np.sum(e1[None, :, :] * pvec, axis=-1)
    usable = np.abs(det) > 1e-12
    inverse = 1.0 / np.where(usable, det, 1.0)
    tvec = origins[:, None, :] - v0[None, :, :]
    u = np.sum(tvec * pvec, axis=-1) * inverse
    qvec = np.cross(tvec, e1[None, :, :])
    v = np.sum(directions[:, None, :] * qvec, axis=-1) * inverse
    t = np.sum(e2[None, :, :] * qvec, axis=-1) * inverse
    hit = usable & (u >= 0) & (v >= 0) & (u + v <= 1) & (t > EPS)
    return np.where(hit, t, INF).astype(F)


def _rays_near(triangles, k, origins, directions):
    """Indices of the rays whose line ahead meets instance k's sphere."""
    oc = triangles["centre"][k][None, :] - origins
    b = np.sum(oc * directions, axis=-1)
    disc = b * b - (np.sum(oc * oc, axis=-1) - triangles["radius"][k] ** 2)
    return np.nonzero((disc > 0) & (b + np.sqrt(np.maximum(disc, 0)) > 0))[0]


def _hit_triangles(triangles, origins, directions, chunk=8192):
    """Nearest triangle per ray: (t, geometric normal, albedo); t = INF for none."""
    best = np.full(len(origins), INF, F)
    normal = np.zeros((len(origins), 3), F)
    albedo = np.zeros((len(origins), 3), F)
    for k in range(len(triangles["centre"])):
        near = _rays_near(triangles, k, origins, directions)
        for rays in np.array_split(near, max(1, -(-len(near) // chunk))):
            if not len(rays):
                continue
            t = _triangle_distances(
                origins[rays], directions[rays], triangles["v0"][k], triangles["e1"][k], triangles["e2"][k]
            )
            which = np.argmin(t, axis=1)
            t = t[np.arange(len(rays)), which]
            closer = t < best[rays]
            rays, which = rays[closer], which[closer]
            best[rays] = t[closer]
            normal[rays] = _normalize(np.cross(triangles["e1"][k][which], triangles["e2"][k][which]))
            albedo[rays] = triangles["albedo"][k]
    return best, normal, albedo


def _any_triangle_towards(triangles, origins, directions, chunk=8192):
    blocked = np.zeros(len(origins), bool)
    for k in range(len(triangles["centre"])):
        near = _rays_near(triangles, k, origins, directions)
        near = near[~blocked[near]]
        for rays in np.array_split(near, max(1, -(-len(near) // chunk))):
            if len(rays):
                t = _triangle_distances(
                    origins[rays], directions[rays], triangles["v0"][k], triangles["e1"][k], triangles["e2"][k]
                )
                blocked[rays] = (t < INF).any(axis=1)
    return blocked


def _sky(scene, directions):
    blend = np.clip(directions[:, 1], 0, 1)[:, None]
    base = (1 - blend) * scene["sky_horizon"][None, :] + blend * scene["sky_zenith"][None, :]
    disc = (directions @ scene["sun_direction"] > 0.9995)[:, None]
    return (base + disc * scene["sun_color"][None, :] * 8.0).astype(F)


def _cosine_direction(normals, rng):
    u1 = rng.random(len(normals), dtype=F)
    u2 = rng.random(len(normals), dtype=F)
    r, phi = np.sqrt(u1), F(2 * np.pi) * u2
    helper = np.where(
        np.abs(normals[:, :1]) > 0.9, np.array([[0, 1, 0]], F), np.array([[1, 0, 0]], F)
    )
    tangent = _normalize(np.cross(helper, normals))
    bitangent = np.cross(normals, tangent)
    z = np.sqrt(np.maximum(0, 1 - u1))
    return (
        (r * np.cos(phi))[:, None] * tangent
        + (r * np.sin(phi))[:, None] * bitangent
        + z[:, None] * normals
    ).astype(F)


def trace(scene: dict, origins, directions, rng, max_bounces: int, triangles: dict | None = None) -> np.ndarray:
    """Radiance of one path per ray, [R, 3]. Paths that ended leave the
    working set, so later bounces cost less."""
    radiance = np.zeros((len(origins), 3), F)
    lanes = np.arange(len(origins))
    throughput = np.ones((len(origins), 3), F)
    for _ in range(max_bounces):
        if not len(lanes):
            break
        t_sphere, index = _hit_spheres(scene, origins, directions)
        slope = directions[:, 1]
        safe = np.where(np.abs(slope) < 1e-8, F(1e-8), slope)
        t_plane = np.where(
            (-origins[:, 1] / safe > EPS) & (np.abs(slope) >= 1e-8), -origins[:, 1] / safe, INF
        ).astype(F)
        on_plane = t_plane < t_sphere
        t = np.minimum(t_sphere, t_plane)
        on_mesh = np.zeros(len(t), bool)
        if triangles is not None:
            t_mesh, mesh_normals, mesh_albedo = _hit_triangles(triangles, origins, directions)
            on_mesh = t_mesh < t
            t = np.minimum(t, t_mesh)
            on_plane &= ~on_mesh
            facing = np.sum(mesh_normals * directions, axis=-1) < 0
            mesh_normals = np.where(facing[:, None], mesh_normals, -mesh_normals)
        hit = t < INF
        radiance[lanes[~hit]] += throughput[~hit] * _sky(scene, directions[~hit])

        lanes, origins, directions = lanes[hit], origins[hit], directions[hit]
        throughput, t, index, on_plane, on_mesh = throughput[hit], t[hit], index[hit], on_plane[hit], on_mesh[hit]
        points = origins + directions * t[:, None]
        sphere_normals = (points - scene["centers"][index]) / np.maximum(
            scene["radii"][index][:, None], 1e-6
        )
        normals = np.where(on_plane[:, None], np.array([[0, 1, 0]], F), sphere_normals).astype(F)
        checker = (np.floor(points[:, 0]).astype(np.int64) + np.floor(points[:, 2]).astype(np.int64)) % 2
        plane_albedo = np.where(
            checker[:, None] == 0, scene["plane_albedo_a"][None, :], scene["plane_albedo_b"][None, :]
        )
        albedo = np.where(on_plane[:, None], plane_albedo, scene["albedo"][index]).astype(F)
        emission = np.where(on_plane[:, None], F(0), scene["emission"][index]).astype(F)
        if triangles is not None:
            normals = np.where(on_mesh[:, None], mesh_normals[hit], normals)
            albedo = np.where(on_mesh[:, None], mesh_albedo[hit], albedo)
            emission = np.where(on_mesh[:, None], F(0), emission)
        radiance[lanes] += throughput * emission

        cos_sun = np.maximum(normals @ scene["sun_direction"], 0)
        start = (points + normals * (EPS * 4)).astype(F)
        sun = np.broadcast_to(scene["sun_direction"], start.shape)
        lit = ~_any_sphere_towards(scene, start, sun)
        if triangles is not None:
            ask = np.nonzero(lit & (cos_sun > 0))[0]  # the others' sun term is nothing already
            lit[ask] = ~_any_triangle_towards(triangles, start[ask], sun[ask])
        radiance[lanes] += (
            throughput * albedo * scene["sun_color"][None, :] * (cos_sun * lit)[:, None] / F(np.pi)
        )
        throughput = throughput * albedo
        origins, directions = start, _cosine_direction(normals, rng)
    return radiance


def primary_rays(camera: dict, *, width, height, y0, x0, size, rng):
    ys, xs = np.meshgrid(np.arange(size, dtype=F) + y0, np.arange(size, dtype=F) + x0, indexing="ij")
    px = xs.reshape(-1) + rng.random(size * size, dtype=F)
    py = ys.reshape(-1) + rng.random(size * size, dtype=F)
    tan = camera["tan_half_fov"]
    ndc_x = (px / width * 2 - 1) * (width / height) * tan
    ndc_y = (1 - py / height * 2) * tan
    directions = _normalize(
        camera["forward"][None, :] + ndc_x[:, None] * camera["right"][None, :]
        + ndc_y[:, None] * camera["up"][None, :]
    ).astype(F)
    return np.broadcast_to(camera["origin"], directions.shape).astype(F), directions


def display(linear: np.ndarray) -> np.ndarray:
    """Reinhard + gamma 2.2, as floats in [0, 255]."""
    mapped = linear / (1 + linear)
    return np.power(np.clip(mapped, 0, 1), 1 / 2.2) * 255.0


def render_crop_replicas(
    scene: dict, camera: dict, mesh: dict | None = None, *, width, height, y0, x0, size, samples,
    max_bounces, replicas, seed,
) -> np.ndarray:
    """`replicas` independent renders of one crop: [replicas, size, size, 3]
    display values, each the tonemapped mean of `samples` paths a pixel.
    `mesh` (a mesh scene): object-space `v0`, `e1`, `e2` [T, 3] and the
    instances' `rotation`, `translation`, `scale`, `albedo`."""
    scene = {key: np.asarray(value, F) for key, value in scene.items()}
    camera = {key: np.asarray(value, F) for key, value in camera.items()}
    triangles = world_triangles({key: np.asarray(value, F) for key, value in mesh.items()}) if mesh else None
    rng = np.random.default_rng(seed)
    images = []
    for _ in range(replicas):
        total = np.zeros((size * size, 3), F)
        for _ in range(samples):
            origins, directions = primary_rays(
                camera, width=width, height=height, y0=y0, x0=x0, size=size, rng=rng
            )
            total += trace(scene, origins, directions, rng, max_bounces, triangles)
        images.append(display(total / samples).reshape(size, size, 3))
    return np.stack(images)
