"""`plain_tracer`'s path tracer for meshes too large to test triangle by
triangle: the same semantics (its docstring is the specification), the same
use of the random numbers, NumPy float32, with an acceleration structure of
its own.

`plain_tracer` tests every ray against every triangle of every instance it
comes near, `[rays, T]` matrices in chunks of 8192 rays: at the 871,200
triangles of the configuration `03ph2scan-480f-1w` that is 7e9 elements a
chunk. Here the triangles handed over by the program (plain `[T, 3]` arrays
in object space: `lib/region_child.py`) are sorted into a balanced
median-split tree built in this file, and rays are taken into an instance's
object space (x_obj = rotation^T (x_world - translation) / scale, which
keeps the ray's parameter in world units) and walked through that one tree,
all rays and instances at once, a level at a time. Nothing here is shared
with the program's own tree (`render/mesh.py`): other splits, other leaf
size, other order, another traversal.

The sphere, plane, sky, sun and sampling steps are `plain_tracer`'s own
functions, and the random numbers are drawn in its order, so on a mesh both
can trace the two agree to rounding.

It refuses to stand as a reference where the program hands it no mesh, a
mesh that is not the one a configuration states (`stated_bodies`: every
configuration whose `check.independent.reference` names this module says in
`deployment` how many bodies there are and how many triangles a body has), or
a surface that is not closed: a program that does not know the scene family
renders some other scene, and a reference computed from that scene's arrays
would agree with it; a builder that drops, doubles or moves a triangle leaves
an edge that two triangles no longer share.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmark.reference.plain_tracer import (
    EPS, INF, F, _any_sphere_towards, _cosine_direction, _hit_spheres, _normalize, _sky,
    display, primary_rays,
)

LEAF_TRIANGLES = 8
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


class Refused(RuntimeError):
    """The arrays handed over cannot be the configuration's scene."""


def stated_bodies() -> list[tuple[str, int, int]]:
    """(configuration, bodies, triangles a body) of every configuration
    that names this module as its independent reference. `lib/check.py`
    hands a reference the arrays and the shape but not the configuration,
    so the arrays are held against every one of them."""
    stated = []
    for path in sorted(CONFIGS.glob("*/config.json")):
        config = json.loads(path.read_text())
        if config.get("check", {}).get("independent", {}).get("reference") != Path(__file__).stem:
            continue
        deployment = config.get("deployment", {})
        if "bodies" not in deployment or "triangles_per_body" not in deployment:
            raise Refused(
                f"plain_tracer_accel: configuration {config['name']} names this reference and does "
                "not state deployment.bodies and deployment.triangles_per_body"
            )
        stated.append((config["name"], int(deployment["bodies"]), int(deployment["triangles_per_body"])))
    return stated


def unshared_edges(v0: np.ndarray, e1: np.ndarray, e2: np.ndarray) -> int:
    """How many edges of the triangles are not shared by exactly two of
    them: 0 for a closed surface. Corners are matched by position (the
    arrays carry no indices): along each axis, coordinates closer than a
    few float32 roundings of the mesh's extent are one value, and a corner
    is its three values. Triangles at other places than their neighbours
    expect, a missing one and one held twice all leave such edges."""
    corners = np.stack([v0, v0 + e1, v0 + e2], axis=1).astype(np.float64).reshape(-1, 3)
    if not len(corners):
        return 0
    tolerance = 4.0 * float(np.finfo(np.float32).eps) * float(np.ptp(corners, axis=0).max())
    labels = np.empty(corners.shape, np.int64)
    for axis in range(3):
        order = np.argsort(corners[:, axis], kind="stable")
        steps = np.diff(corners[order, axis]) > tolerance
        labels[order, axis] = np.concatenate([[0], np.cumsum(steps)])
    corner = np.unique(labels, axis=0, return_inverse=True)[1].reshape(-1, 3)
    ends = np.concatenate([corner[:, [0, 1]], corner[:, [1, 2]], corner[:, [2, 0]]])
    ends.sort(axis=1)
    shared = np.unique(ends[:, 0] * (corner.max() + 1) + ends[:, 1], return_counts=True)[1]
    return int((shared != 2).sum())


class Tree:
    """A balanced binary tree over triangles, median splits along the
    longest side of the centroids' box, stored by level: level `d` has
    `2**d` nodes, node `i` of it holds the sorted triangles
    `starts[d][i] : starts[d][i + 1]`, and its children are nodes `2i` and
    `2i + 1` of the next level. The last level's nodes are the leaves, of
    at most `LEAF_TRIANGLES` triangles."""

    def __init__(self, v0: np.ndarray, e1: np.ndarray, e2: np.ndarray):
        # rows of padding (all zero) cannot be hit and would only widen boxes
        real = (np.abs(e1).sum(axis=1) > 0) & (np.abs(e2).sum(axis=1) > 0)
        v0, e1, e2 = v0[real], e1[real], e2[real]
        corners = np.stack([v0, v0 + e1, v0 + e2], axis=1)  # [T, 3, 3]
        centroids = corners.mean(axis=1)
        count = len(v0)
        depth = max(0, int(np.ceil(np.log2(max(count, 1) / LEAF_TRIANGLES))))
        order = np.arange(count)
        starts = [np.array([0, count])]
        for _ in range(depth):
            edges = starts[-1]
            sizes = np.diff(edges)
            node = np.repeat(np.arange(len(sizes)), sizes)
            sorted_centroids = centroids[order]
            nonempty = sizes > 0
            low = np.full((len(sizes), 3), np.inf)
            high = np.full((len(sizes), 3), -np.inf)
            low[nonempty] = np.minimum.reduceat(sorted_centroids, edges[:-1][nonempty])
            high[nonempty] = np.maximum.reduceat(sorted_centroids, edges[:-1][nonempty])
            axis = np.argmax(np.where(nonempty[:, None], high - low, 0.0), axis=1)
            key = sorted_centroids[np.arange(count), axis[node]]
            order = order[np.lexsort((key, node))]
            middle = edges[:-1] + sizes // 2
            starts.append(np.append(np.stack([edges[:-1], middle], axis=1).reshape(-1), count))
        self.v0, self.e1, self.e2 = v0[order], e1[order], e2[order]
        self.normal = _normalize(np.cross(self.e1, self.e2)).astype(F)
        self.starts = starts
        corners = corners[order]
        low_of, high_of = corners.min(axis=1), corners.max(axis=1)
        self.low, self.high = [], []
        for edges in starts:
            sizes = np.diff(edges)
            nonempty = sizes > 0
            low = np.full((len(sizes), 3), np.inf, F)
            high = np.full((len(sizes), 3), -np.inf, F)
            low[nonempty] = np.minimum.reduceat(low_of, edges[:-1][nonempty])
            high[nonempty] = np.maximum.reduceat(high_of, edges[:-1][nonempty])
            self.low.append(low)
            self.high.append(high)
        self.reach = float(np.linalg.norm(corners.reshape(-1, 3), axis=-1).max()) if count else 0.0

    def candidates(self, origins, directions, limits):
        """(query, triangle) pairs of every triangle in a leaf whose box
        query `q`'s ray meets between 0 and `limits[q]`."""
        safe = np.where(np.abs(directions) < 1e-12, np.where(directions < 0, -1e-12, 1e-12), directions)
        inverse = (1.0 / safe).astype(F)
        query = np.arange(len(origins))
        node = np.zeros(len(origins), np.int64)
        last = len(self.starts) - 1
        for level in range(last + 1):
            a = (self.low[level][node] - origins[query]) * inverse[query]
            b = (self.high[level][node] - origins[query]) * inverse[query]
            enter = np.minimum(a, b).max(axis=1)
            leave = np.maximum(a, b).min(axis=1)
            keep = (leave >= np.maximum(enter, 0)) & (enter < limits[query])
            query, node = query[keep], node[keep]
            if level < last:
                query = np.repeat(query, 2)
                node = (np.repeat(node, 2) * 2) + np.tile(np.array([0, 1]), len(node))
        first = self.starts[last][node]
        sizes = self.starts[last][node + 1] - first
        query = np.repeat(query, sizes)
        within = np.arange(len(query)) - np.repeat(np.cumsum(sizes) - sizes, sizes)
        return query, np.repeat(first, sizes) + within

    def distances(self, origins, directions, query, triangle):
        """Moeller-Trumbore of pair `(query[i], triangle[i])`; INF = miss."""
        o, d = origins[query], directions[query]
        v0, e1, e2 = self.v0[triangle], self.e1[triangle], self.e2[triangle]
        pvec = np.cross(d, e2)
        det = np.sum(e1 * pvec, axis=-1)
        usable = np.abs(det) > 1e-12
        inverse = 1.0 / np.where(usable, det, 1.0)
        tvec = o - v0
        u = np.sum(tvec * pvec, axis=-1) * inverse
        qvec = np.cross(tvec, e1)
        v = np.sum(d * qvec, axis=-1) * inverse
        t = np.sum(e2 * qvec, axis=-1) * inverse
        hit = usable & (u >= 0) & (v >= 0) & (u + v <= 1) & (t > EPS)
        return np.where(hit, t, INF).astype(F)


class Bodies:
    """The instances of one tree: what `plain_tracer.world_triangles` is
    to the plain tracer."""

    def __init__(self, mesh: dict):
        self.tree = Tree(mesh["v0"], mesh["e1"], mesh["e2"])
        self.rotation, self.translation = mesh["rotation"], mesh["translation"]
        self.scale, self.albedo = mesh["scale"], mesh["albedo"]
        self.radius = self.scale * F(self.tree.reach) * F(1.001) + EPS

    def queries(self, origins, directions):
        """One query per (ray, instance) whose line ahead meets the sphere
        round the instance: ray index, instance, object-space ray."""
        rays, instances = [], []
        for k in range(len(self.scale)):
            oc = self.translation[k][None, :] - origins
            b = np.sum(oc * directions, axis=-1)
            disc = b * b - (np.sum(oc * oc, axis=-1) - self.radius[k] ** 2)
            near = (disc > 0) & (b + np.sqrt(np.maximum(disc, 0)) > 0)
            rays.append(np.nonzero(near)[0])
            instances.append(np.full(len(rays[-1]), k))
        ray, instance = np.concatenate(rays), np.concatenate(instances)
        inverse_scale = (1.0 / self.scale[instance])[:, None]
        turned = np.transpose(self.rotation[instance], (0, 2, 1))  # rotation^T
        local_o = np.einsum("qij,qj->qi", turned, origins[ray] - self.translation[instance]) * inverse_scale
        local_d = np.einsum("qij,qj->qi", turned, directions[ray]) * inverse_scale
        return ray, instance, local_o.astype(F), local_d.astype(F)

    def nearest(self, origins, directions):
        """Nearest triangle per ray: (t, geometric normal, albedo)."""
        best = np.full(len(origins), INF, F)
        normal = np.zeros((len(origins), 3), F)
        albedo = np.zeros((len(origins), 3), F)
        ray, instance, local_o, local_d = self.queries(origins, directions)
        query, triangle = self.tree.candidates(local_o, local_d, np.full(len(ray), INF, F))
        t = self.tree.distances(local_o, local_d, query, triangle)
        # the nearest candidate of each ray, the first listed at a tie
        order = np.lexsort((np.arange(len(t)), t, ray[query]))
        order = order[np.concatenate([[True], np.diff(ray[query][order]) > 0])] if len(order) else order
        order = order[t[order] < INF]
        hit, who = ray[query[order]], instance[query[order]]
        best[hit] = t[order]
        normal[hit] = np.einsum("qij,qj->qi", self.rotation[who], self.tree.normal[triangle[order]])
        albedo[hit] = self.albedo[who]
        return best, normal, albedo

    def any_towards(self, origins, directions):
        ray, _, local_o, local_d = self.queries(origins, directions)
        query, triangle = self.tree.candidates(local_o, local_d, np.full(len(ray), INF, F))
        blocked = np.zeros(len(origins), bool)
        blocked[ray[query[self.tree.distances(local_o, local_d, query, triangle) < INF]]] = True
        return blocked


def trace(scene: dict, origins, directions, rng, max_bounces: int, bodies: Bodies) -> np.ndarray:
    """`plain_tracer.trace` with the triangles asked of `bodies`: the same
    steps in the same order, the same random numbers drawn."""
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
        t_mesh, mesh_normals, mesh_albedo = bodies.nearest(origins, directions)
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
        normals = np.where(on_mesh[:, None], mesh_normals[hit], normals)
        albedo = np.where(on_mesh[:, None], mesh_albedo[hit], albedo)
        emission = np.where(on_mesh[:, None], F(0), emission)
        radiance[lanes] += throughput * emission

        cos_sun = np.maximum(normals @ scene["sun_direction"], 0)
        start = (points + normals * (EPS * 4)).astype(F)
        sun = np.broadcast_to(scene["sun_direction"], start.shape)
        lit = ~_any_sphere_towards(scene, start, sun)
        ask = np.nonzero(lit & (cos_sun > 0))[0]  # the others' sun term is nothing already
        lit[ask] = ~bodies.any_towards(start[ask], sun[ask])
        radiance[lanes] += (
            throughput * albedo * scene["sun_color"][None, :] * (cos_sun * lit)[:, None] / F(np.pi)
        )
        throughput = throughput * albedo
        origins, directions = start, _cosine_direction(normals, rng)
    return radiance


def render_crop_replicas(
    scene: dict, camera: dict, mesh: dict | None = None, *, width, height, y0, x0, size, samples,
    max_bounces, replicas, seed, min_triangles: int | None = None,
) -> np.ndarray:
    """`plain_tracer.render_crop_replicas` for a mesh scene of many
    triangles. The mesh has to be one that a configuration states (bodies
    and triangles a body, `stated_bodies`) and a closed surface. A test of
    a mesh no configuration states gives `min_triangles`, the least the
    mesh may hold, in the configurations' place."""
    stated = stated_bodies() if min_triangles is None else []
    said = ", ".join(f"{name}: {bodies} bodies of {count} triangles" for name, bodies, count in stated)
    if min_triangles is None and not stated:
        raise Refused("plain_tracer_accel: no configuration names this reference, so none states the mesh")
    if not mesh:
        raise Refused(
            "plain_tracer_accel: the program handed over no mesh: it does not render the scene of "
            f"a configuration that names this reference ({said or f'{min_triangles} triangles a body'})"
        )
    mesh = {key: np.asarray(value, F) for key, value in mesh.items()}
    real = (np.abs(mesh["e1"]).sum(axis=1) > 0) & (np.abs(mesh["e2"]).sum(axis=1) > 0)
    held, bodies = int(real.sum()), len(mesh["scale"])
    if min_triangles is not None and held < min_triangles:
        raise Refused(f"plain_tracer_accel: the program's mesh holds {held} triangles, {min_triangles} wanted")
    if min_triangles is None and (bodies, held) not in {(b, count) for _, b, count in stated}:
        raise Refused(
            f"plain_tracer_accel: the program's mesh is {bodies} bodies of {held} triangles, and the "
            f"configurations state {said}: not a configuration's scene"
        )
    unshared = unshared_edges(mesh["v0"][real], mesh["e1"][real], mesh["e2"][real])
    if unshared:
        raise Refused(
            f"plain_tracer_accel: {unshared} edges of the program's mesh are not shared by exactly two "
            "triangles: the surface is not closed, so it is not the mesh the generator made"
        )
    scene = {key: np.asarray(value, F) for key, value in scene.items()}
    camera = {key: np.asarray(value, F) for key, value in camera.items()}
    bodies = Bodies(mesh)
    rng = np.random.default_rng(seed)
    images = []
    for _ in range(replicas):
        total = np.zeros((size * size, 3), F)
        for _ in range(samples):
            origins, directions = primary_rays(
                camera, width=width, height=height, y0=y0, x0=x0, size=size, rng=rng
            )
            total += trace(scene, origins, directions, rng, max_bounces, bodies)
        images.append(display(total / samples).reshape(size, size, 3))
    return np.stack(images)
