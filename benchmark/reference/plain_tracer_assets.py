"""`plain_tracer_accel`'s path tracer for a scene whose bodies are several
different meshes: the same semantics (`plain_tracer`'s docstring is the
specification), the same use of the random numbers, NumPy float32, with
one median-split tree a model, built here from the plain arrays.

`plain_tracer_accel` is "the instances of one tree" and states one
`triangles_per_body`. Here the program hands over the models' triangles end
to end (`mesh_v0` / `mesh_e1` / `mesh_e2`) and, for every instance, which
model it is and which rows are that model's (`mesh_model`, `mesh_tri_first`,
`mesh_tri_count`: the fields of the program's `MeshInstances` as
`lib/region_child.py` writes them). Each model's rows become a
`plain_tracer_accel.Bodies` of its own (that file's tree and walk, a
benchmark file; nothing is shared with the program's `render/mesh.py`) over
the instances that name it; a ray's nearest hit is the nearest over the
models, the first model's at a tie, and it is shadowed where any model
shadows it. The mesh steps draw no random numbers, so with one model this
is `plain_tracer_accel` to the last bit.

It refuses to stand as a reference where the arrays are not the scene a
configuration states: `deployment.bodies` bodies, body `i` an instance of
model `i mod M` of `deployment.models` (each with its `generated_triangles`),
the models' rows end to end in that order, each a closed surface. A program
that does not know the scene family renders some other scene, and a
reference computed from that scene's arrays would agree with it; a program
that gives every body one model's BLAS hands over one model.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmark.reference.plain_tracer import INF, F, display, primary_rays
from benchmark.reference import plain_tracer_accel
from benchmark.reference.plain_tracer_accel import CONFIGS, Bodies, Refused, trace

NAME = Path(__file__).stem


def stated_scenes() -> list[tuple[str, int, list[int]]]:
    """(configuration, bodies, generated triangles of each model in order)
    of every configuration that names this module as its independent
    reference. `lib/check.py` hands a reference the arrays and the shape but
    not the configuration, so the arrays are held against every one."""
    stated = []
    for path in sorted(CONFIGS.glob("*/config.json")):
        config = json.loads(path.read_text())
        if config.get("check", {}).get("independent", {}).get("reference") != NAME:
            continue
        deployment = config.get("deployment", {})
        models = deployment.get("models")
        if "bodies" not in deployment or not models or any("generated_triangles" not in m for m in models):
            raise Refused(
                f"{NAME}: configuration {config['name']} names this reference and does not state "
                "deployment.bodies and deployment.models with each model's generated_triangles"
            )
        stated.append((config["name"], int(deployment["bodies"]), [int(m["generated_triangles"]) for m in models]))
    return stated


def models_handed_over(mesh: dict) -> list[tuple[int, int, int]]:
    """(first row, rows, real triangles) of each model as the instances
    name them, in the models' order; refuses arrays that name none, a body
    that is not model `i mod M`, or rows that do not lie end to end."""
    for key in ("model", "tri_first", "tri_count"):
        if key not in mesh:
            raise Refused(
                f"{NAME}: the program's instances carry no {key!r}: it does not know that a scene's "
                "bodies can be different models"
            )
    model = np.asarray(mesh["model"]).astype(np.int64)
    first = np.asarray(mesh["tri_first"]).astype(np.int64)
    count = np.asarray(mesh["tri_count"]).astype(np.int64)
    held = int(model.max()) + 1
    if not np.array_equal(model, np.arange(len(model)) % held):
        raise Refused(f"{NAME}: body i is not model i mod {held}: the bodies' models are {model.tolist()}")
    real = (np.abs(mesh["e1"]).sum(axis=1) > 0) & (np.abs(mesh["e2"]).sum(axis=1) > 0)
    models, end = [], 0
    for m in range(held):
        rows = {(int(a), int(b)) for a, b in zip(first[model == m], count[model == m])}
        if len(rows) != 1 or next(iter(rows))[0] != end:
            raise Refused(f"{NAME}: the rows of model {m} are {sorted(rows)}, and the model before it ends at {end}")
        ((start, size),) = rows
        end = start + size
        models.append((start, size, int(real[start:end].sum())))
    if end != len(mesh["v0"]):
        raise Refused(f"{NAME}: the models hold {end} rows of the {len(mesh['v0'])} handed over")
    return models


def unshared_edges(v0: np.ndarray, e1: np.ndarray, e2: np.ndarray) -> int:
    """`plain_tracer_accel.unshared_edges` (how many edges are not shared by
    exactly two triangles; corners matched by position, coordinates within
    a few float32 roundings one value) with a corner's three values packed
    into one integer, so that corners are told apart by a sort of numbers
    and not of rows: a quarter of the time on a million triangles, and the
    run that computes a reference has a time limit. Where the three do not
    fit one integer it is that function."""
    corners = np.stack([v0, v0 + e1, v0 + e2], axis=1).astype(np.float64).reshape(-1, 3)
    if not len(corners):
        return 0
    tolerance = 4.0 * float(np.finfo(np.float32).eps) * float(np.ptp(corners, axis=0).max())
    key, room = np.zeros(len(corners), np.int64), 1
    for axis in range(3):
        order = np.argsort(corners[:, axis], kind="stable")
        labels = np.empty(len(corners), np.int64)
        labels[order] = np.concatenate([[0], np.cumsum(np.diff(corners[order, axis]) > tolerance)])
        values = int(labels.max()) + 1
        key, room = key * values + labels, room * values
    if room >= 1 << 62:
        return plain_tracer_accel.unshared_edges(v0, e1, e2)
    corner = np.unique(key, return_inverse=True)[1].reshape(-1, 3)
    ends = np.concatenate([corner[:, [0, 1]], corner[:, [1, 2]], corner[:, [2, 0]]])
    ends.sort(axis=1)
    shared = np.unique(ends[:, 0] * (corner.max() + 1) + ends[:, 1], return_counts=True)[1]
    return int((shared != 2).sum())


class AssetBodies:
    """The instances of several trees: one `plain_tracer_accel.Bodies` a
    model over the instances that name it, asked in the models' order."""

    def __init__(self, mesh: dict, models: list[tuple[int, int, int]]):
        self.groups = []
        for m, (start, count, _) in enumerate(models):
            members = np.asarray(mesh["model"]).astype(np.int64) == m
            rows = slice(start, start + count)
            self.groups.append(Bodies({
                "v0": mesh["v0"][rows], "e1": mesh["e1"][rows], "e2": mesh["e2"][rows],
                **{key: mesh[key][members] for key in ("rotation", "translation", "scale", "albedo")},
            }))

    def nearest(self, origins, directions):
        best = np.full(len(origins), INF, F)
        normal = np.zeros((len(origins), 3), F)
        albedo = np.zeros((len(origins), 3), F)
        for group in self.groups:
            t, group_normal, group_albedo = group.nearest(origins, directions)
            closer = t < best
            best = np.where(closer, t, best)
            normal = np.where(closer[:, None], group_normal, normal)
            albedo = np.where(closer[:, None], group_albedo, albedo)
        return best, normal, albedo

    def any_towards(self, origins, directions):
        blocked = np.zeros(len(origins), bool)
        for group in self.groups:
            blocked |= group.any_towards(origins, directions)
        return blocked


def render_crop_replicas(
    scene: dict, camera: dict, mesh: dict | None = None, *, width, height, y0, x0, size, samples,
    max_bounces, replicas, seed, stated: tuple[int, list[int]] | None = None,
) -> np.ndarray:
    """`plain_tracer.render_crop_replicas` for a scene of several models.
    The arrays have to be a scene that a configuration states (bodies, and
    each model's triangles in order: `stated_scenes`) and every model a
    closed surface. A test of meshes no configuration states gives
    `stated` = (bodies, [triangles of each model]) in the configurations'
    place."""
    scenes = [("the test", *stated)] if stated is not None else stated_scenes()
    said = "; ".join(f"{name}: {bodies} bodies over models of {counts} triangles" for name, bodies, counts in scenes)
    if not scenes:
        raise Refused(f"{NAME}: no configuration names this reference, so none states the models")
    if not mesh:
        raise Refused(
            f"{NAME}: the program handed over no mesh: it does not render the scene of a configuration "
            f"that names this reference ({said})"
        )
    integers = ("model", "tri_first", "tri_count")
    mesh = {key: np.asarray(value) if key in integers else np.asarray(value, F) for key, value in mesh.items()}
    models = models_handed_over(mesh)
    handed = (len(mesh["scale"]), [real for _, _, real in models])
    if handed not in [(bodies, counts) for _, bodies, counts in scenes]:
        raise Refused(
            f"{NAME}: the program's mesh is {handed[0]} bodies over models of {handed[1]} triangles, and "
            f"the configurations state {said}: not a configuration's scene"
        )
    for m, (start, count, _) in enumerate(models):
        rows = slice(start, start + count)
        real = (np.abs(mesh["e1"][rows]).sum(axis=1) > 0) & (np.abs(mesh["e2"][rows]).sum(axis=1) > 0)
        unshared = unshared_edges(mesh["v0"][rows][real], mesh["e1"][rows][real], mesh["e2"][rows][real])
        if unshared:
            raise Refused(
                f"{NAME}: {unshared} edges of model {m} are not shared by exactly two triangles: the "
                "surface is not closed, so it is not the mesh the generator made"
            )
    scene = {key: np.asarray(value, F) for key, value in scene.items()}
    camera = {key: np.asarray(value, F) for key, value in camera.items()}
    bodies = AssetBodies(mesh, models)
    rng = np.random.default_rng(seed)
    images = []
    for _ in range(replicas):
        total = np.zeros((size * size, 3), F)
        for _ in range(samples):
            origins, directions = primary_rays(camera, width=width, height=height, y0=y0, x0=x0, size=size, rng=rng)
            total += trace(scene, origins, directions, rng, max_bounces, bodies)
        images.append(display(total / samples).reshape(size, size, 3))
    return np.stack(images)
