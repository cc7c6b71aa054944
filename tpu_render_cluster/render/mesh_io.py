"""OBJ (.obj) mesh ingest for the TPU mesh path.

The reference's workers render arbitrary user content by shelling out to
Blender (reference: worker/src/rendering/runner/mod.rs:165-176 — whatever
the .blend contains). The TPU tracer's counterpart for user geometry is
this loader: triangles from an OBJ file feed the same host-built threaded
BVH (`mesh.build_bvh`) and traverse with the same Pallas kernels as the
procedural meshes — topology is loaded once on the host and becomes
static device arrays, so arbitrary meshes compose into jit/vmap exactly
like the built-ins.

Supported OBJ subset: `v` positions, `f` faces with any of the index
forms (`v`, `v/vt`, `v/vt/vn`, `v//vn`), negative (relative) indices,
absolute indices forward-referencing later `v` lines, polygon faces
(triangulated as a fan), comments, and all other statements ignored (normals are recomputed per-face by `build_bvh`; materials are a
per-instance albedo in this renderer).
"""

from __future__ import annotations

import functools
from pathlib import Path

import numpy as np


def load_obj(path: str | Path) -> tuple[np.ndarray, np.ndarray]:
    """Parse an OBJ file into (vertices [V,3] f32, faces [F,3] i32)."""
    vertices: list[tuple[float, float, float]] = []
    # Faces are collected as raw tokens and resolved only after the whole
    # file is read: absolute indices may legally forward-reference `v`
    # lines that appear later. Negative (relative) indices are resolved
    # against the vertex count AT the `f` statement, per the OBJ spec, so
    # that count is recorded alongside the tokens.
    pending_faces: list[tuple[int, int, list[str]]] = []

    with open(path, encoding="utf-8", errors="replace") as handle:
        for line_number, line in enumerate(handle, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            if parts[0] == "v":
                if len(parts) < 4:
                    # Must be fatal: silently dropping a malformed vertex
                    # would shift every subsequent face index.
                    raise ValueError(
                        f"{path}:{line_number}: vertex needs 3 coordinates"
                    )
                vertices.append(
                    (float(parts[1]), float(parts[2]), float(parts[3]))
                )
            elif parts[0] == "f":
                if len(parts) < 4:
                    raise ValueError(
                        f"{path}:{line_number}: face needs >=3 vertices"
                    )
                pending_faces.append((line_number, len(vertices), parts[1:]))
            # vn/vt/o/g/s/usemtl/mtllib: ignored (see module docstring).

    def resolve(token: str, line_number: int, vertex_count_at_face: int) -> int:
        # "v", "v/vt", "v/vt/vn", "v//vn" -> vertex index (1-based;
        # negative = relative to the vertices seen up to the f statement).
        raw = token.split("/", 1)[0]
        index = int(raw)
        if index < 0:
            index += vertex_count_at_face
            if index < 0:
                raise ValueError(
                    f"{path}:{line_number}: OBJ relative index out of range: {token}"
                )
            return index
        if not 1 <= index <= len(vertices):
            raise ValueError(
                f"{path}:{line_number}: OBJ vertex index out of range: {token}"
            )
        return index - 1

    faces: list[tuple[int, int, int]] = []
    for line_number, vertex_count_at_face, tokens in pending_faces:
        ring = [resolve(token, line_number, vertex_count_at_face) for token in tokens]
        for i in range(1, len(ring) - 1):  # fan triangulation
            faces.append((ring[0], ring[i], ring[i + 1]))

    if not vertices or not faces:
        raise ValueError(f"{path}: no triangles found")
    return (
        np.asarray(vertices, np.float32),
        np.asarray(faces, np.int32),
    )


def normalize_to_stage(
    vertices: np.ndarray, *, target_extent: float = 2.0
) -> np.ndarray:
    """Center the mesh at the origin and scale its largest extent to
    ``target_extent`` — user OBJs arrive in arbitrary units, the stage
    scene (cli --obj) expects roughly unit-scale geometry resting above
    the ground plane."""
    lo = vertices.min(axis=0)
    hi = vertices.max(axis=0)
    center = 0.5 * (lo + hi)
    extent = float((hi - lo).max())
    scale = target_extent / max(extent, 1e-9)
    return ((vertices - center) * scale).astype(np.float32)


@functools.lru_cache(maxsize=8)
def _cached_obj_bvh_impl(resolved: str, mtime_ns: int):
    from tpu_render_cluster.render.mesh import build_bvh

    vertices, faces = load_obj(resolved)
    return build_bvh(normalize_to_stage(vertices), faces)


def cached_obj_bvh(path: str | Path):
    """BVH for an OBJ file, cached on (path, mtime) like the procedural
    meshes are cached on kind."""
    resolved = Path(path).resolve()
    return _cached_obj_bvh_impl(str(resolved), resolved.stat().st_mtime_ns)
