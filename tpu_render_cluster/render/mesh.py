"""Triangle meshes with a threaded BVH, TPU-first.

The reference's workers render arbitrary .blend content (reference:
worker/src/rendering/runner/mod.rs:165-176); this module is the TPU-native
counterpart for mesh geometry (SURVEY.md §7 hard part #4: "BVH on TPU").

Design for the TPU's execution model:

- **Static topology, host-built BVH.** Mesh topology never changes across
  frames; animation is rigid per-instance motion. The BVH is built once on
  the host (numpy, median split) over object-space triangles and becomes
  constant device arrays — no per-frame rebuild, no dynamic shapes.
- **Threaded (skip-link) layout = stackless traversal.** Nodes are stored
  in DFS preorder; each carries a ``skip`` link to the next subtree root.
  Traversal is a single moving index: AABB hit on an inner node -> step to
  ``i + 1``; leaf or miss -> jump to ``skip[i]``. No stack, one scalar of
  control state — exactly what ``lax.while_loop`` (and a Pallas scalar
  loop) wants.
- **Packet traversal.** One node sequence is walked per ray *block*; the
  AABB test is vectorized over the block and reduced with ``any``. The
  scalar unit steers, the vector unit tests — divergence costs extra node
  visits, not scalar-per-ray control flow. Camera/shadow packets are
  coherent, so the shared walk skips most of the tree in practice.
- **Instances, not world-space soup.** Rays are transformed into object
  space per instance (rigid transforms preserve t), so K animated
  instances share one static BVH.

``intersect_triangles_brute`` (batched Möller–Trumbore over all
triangles) is the correctness reference the BVH paths are tested against.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Plain Python floats: a module-level jnp constant would be created during
# whatever trace first imports this module (the integrator imports it
# lazily inside traced functions) and leak that trace's tracer into every
# later caller.
INF = 1e30
EPS = 1e-3
# Fixed leaf width: every leaf occupies its own LEAF_SIZE-aligned slot of
# exactly LEAF_SIZE triangle rows (real triangles first, degenerate padding
# after), and traversal always loads exactly LEAF_SIZE rows masked by the
# node's count. A static aligned width keeps the traversal free of
# shape-dependent Python AND makes the Pallas kernel's dynamic sublane
# slices tile-aligned (8 = the f32 sublane tile).
LEAF_SIZE = 16


class OctantTables(NamedTuple):
    """Per-direction-octant threaded node tables ([8*N] rows, octant o's
    table at rows [o*N, (o+1)*N)): the SAME tree re-threaded eight times
    with children ordered NEAR-FIRST along each octant's sign vector.

    A packet whose direction lies in octant o walks table o and reaches
    near subtrees before far ones, so best-t shrinks early and the
    ``tnear < best_t`` cull rejects far subtrees the fixed-DFS walk
    still visits (measured ~1.4x fewer leaf visits on coherent
    packets). Skip links are LOCAL (0..N); leaf ``first`` slots point
    into the shared triangle rows, so only node order differs. Emitted
    by the ``sah`` builder; any order is exact (per-lane results are
    visit-order invariant, strict-< best-t updates).
    """

    bounds_min: jnp.ndarray  # [8N, 3]
    bounds_max: jnp.ndarray  # [8N, 3]
    skip: jnp.ndarray  # [8N] int32 — LOCAL skip links
    first: jnp.ndarray  # [8N] int32 — shared leaf triangle slots
    count: jnp.ndarray  # [8N] int32


class MeshBVH(NamedTuple):
    """Object-space triangle mesh + threaded BVH (all static device arrays).

    Triangles are stored leaf-reordered so every leaf references the
    contiguous range ``[first, first + count)``. ``octant`` (None on
    median builds) carries the eight near-first-ordered node tables the
    mesh trace kernels walk; the base arrays stay the canonical order
    for the XLA walks and standalone kernels.
    """

    # Triangle data, leaf-contiguous order.
    v0: jnp.ndarray  # [T, 3]
    e1: jnp.ndarray  # [T, 3]  (v1 - v0)
    e2: jnp.ndarray  # [T, 3]  (v2 - v0)
    normal: jnp.ndarray  # [T, 3] unit geometric normals
    # Threaded BVH in DFS preorder.
    bounds_min: jnp.ndarray  # [N, 3]
    bounds_max: jnp.ndarray  # [N, 3]
    skip: jnp.ndarray  # [N] int32 — next subtree root (N = done)
    first: jnp.ndarray  # [N] int32 — leaf triangle start (0 for inner)
    count: jnp.ndarray  # [N] int32 — leaf triangle count (0 for inner)
    octant: "OctantTables | None" = None
    # Set when the BLAS is too large to sit in VMEM/SMEM whole: the
    # tables the bounce kernel streams from HBM by treelet. The fields
    # above are then host (NumPy) arrays — the contract of the tree, for
    # tests and the benchmark's reference — and no traced program reads
    # them (``traced_stream_bvh``).
    stream: "BlasStream | None" = None
    # A set of several BLASes (``morton_bvh_set``): model ``m``'s triangles
    # are rows ``tri_first[m] .. tri_first[m + 1]`` of ``v0`` / ``e1`` /
    # ``e2`` / ``normal`` (host ints). None: one BLAS, every row.
    tri_first: "np.ndarray | None" = None


class BlasStream(NamedTuple):
    """One BLAS or several, cut into treelets, as the device arrays the
    bounce kernel reads from HBM (``pallas_kernels``: ``memory_space=pl.ANY``,
    staged to VMEM scratch by one DMA when a packet enters a treelet).

    The tree's top — every node that holds more leaves than one treelet,
    with the treelet roots as its leaves — stays resident for the whole
    launch, and like everything below it is held as WIDE nodes of eight
    children: the balanced binary tree collapsed three levels at a time
    (``_wide_top``), a wide top node's children the binary nodes three
    levels below it, or a treelet root where one is met sooner, in the
    binary tree's preorder. Below the top, treelet ``t`` is a subtree of at
    most ``treelet_leaves(stream)`` leaves, held as at most nine wide nodes
    in two levels: a root whose children are the subtree's groups (its
    first nodes of at most ``WIDE`` leaves, in preorder), and a node a
    group whose children are the group's leaves. A wide node's eight child
    boxes are tested in one ``[8, block]`` slab test, so a treelet's box is
    tested in its parent's step and a leaf's in its group's. Every static
    size is read from a shape, so the pytree holds arrays only.

    ``tri`` is one slab a treelet, what a fetch copies. Its first
    ``16 * L / 8`` rows pack a triangle as 12 floats (v0, e1, e2, unit
    normal) in 16 lanes, eight leaves side by side across the 128 lanes
    and a leaf's 16 triangles down 16 rows: child ``c`` of group ``g`` is
    leaf slot ``8g + c``, rows ``16g .. 16g + 16``, lanes
    ``16c .. 16c + 12`` (a group is a row block, so the slot needs no
    table; padding rows are zero and meet no ray). That is 64 B a
    triangle in HBM where a ``[T, 3]`` table is 512 B a triangle a table
    on the chip (lanes padded to 128). Its last ``WIDE`` rows are the
    wide nodes' boxes: child ``c`` down the sublanes, and along the
    lanes wide node ``w`` (0 the root, ``1 + g`` group ``g``) at lanes
    ``8w .. 8w + 7``: lo xyz, hi xyz, then the child's bit ``1 << c`` as
    a float (0 for an empty slot, whose box is inverted) and a spare.

    The top's boxes lie in VMEM in that same layout, sixteen wide nodes
    an ``[8, 128]`` tile: wide node ``w`` is rows ``8 (w // 16) ..`` of
    ``top_boxes``, lanes ``8 (w % 16) ..``. What child ``c`` of ``w`` IS
    sits where a scalar load reaches it, word ``8w + c`` of ``top_links``
    in SMEM: ``-1 - v`` for wide top node ``v``, ``t + 1`` for treelet
    ``t``, 0 for an empty slot.
    Wide nodes are numbered in the preorder of their binary nodes, so a
    model's root comes first of its nodes.

    Several BLASes lie end to end in the same tables: model ``m``'s slabs
    follow model ``m - 1``'s in ``tri``, and its top is wide nodes
    ``top_first[m] .. top_first[m + 1]`` of the one top, whose links and
    treelet numbers count from the tables' start. A walk of model ``m``
    begins at its root, wide node ``top_first[m]``, and follows links
    alone, which never leave the model's nodes.
    """

    tri: jnp.ndarray  # [NT, 16 * L/8 + WIDE, 128] f32
    top_boxes: jnp.ndarray  # [8 * ceil(NW / 16), 128] f32
    top_links: jnp.ndarray  # [NW * 8] int32: -1 - wide node | treelet + 1 | 0
    root: jnp.ndarray  # [M, 2, 3] f32: each model's whole tree's bounds
    top_first: jnp.ndarray  # [M + 1] int32: where each model's top begins


# Children of a wide node: the sublanes of one f32 vector register.
WIDE = 8


def treelet_leaves(stream: BlasStream) -> int:
    return (stream.tri.shape[1] - WIDE) // 2


def geometry_bytes(bvh: MeshBVH) -> dict[str, int]:
    """Bytes of a scene's BLAS tables (one BLAS or a set) by the memory
    they live in while a bounce kernel runs: streamed, the treelet tables
    in HBM, the boxes of the trees' wide tops in VMEM and their links in
    SMEM; resident, the padded triangle tables in VMEM and the nodes in
    SMEM (``resident_table_bytes``)."""
    stream = bvh.stream
    if stream is None:
        nodes = bvh.skip.shape[0] * 9 * 4 * (1 if bvh.octant is None else 8)
        return {"hbm": 0, "vmem": bvh.v0.shape[0] * 128 * 4 * 4, "smem": nodes}
    return {
        "hbm": int(stream.tri.size) * 4,
        "vmem": int(stream.top_boxes.size) * 4,
        "smem": int(stream.top_links.size) * 4,
    }


def blas_count(bvh: MeshBVH) -> int:
    """How many BLASes the scene's geometry holds."""
    return 1 if bvh.stream is None else bvh.stream.root.shape[0]


def treelet_fetch_bytes(stream: BlasStream) -> int:
    """Bytes one treelet fetch copies: its triangle rows and wide nodes."""
    return stream.tri.shape[1] * 128 * 4


# ---------------------------------------------------------------------------
# Procedural meshes


def make_box() -> tuple[np.ndarray, np.ndarray]:
    """Unit cube centered at the origin: 8 vertices, 12 triangles."""
    vertices = np.array(
        [
            [-0.5, -0.5, -0.5], [0.5, -0.5, -0.5],
            [0.5, 0.5, -0.5], [-0.5, 0.5, -0.5],
            [-0.5, -0.5, 0.5], [0.5, -0.5, 0.5],
            [0.5, 0.5, 0.5], [-0.5, 0.5, 0.5],
        ],
        np.float32,
    )
    faces = np.array(
        [
            [0, 2, 1], [0, 3, 2],  # -z
            [4, 5, 6], [4, 6, 7],  # +z
            [0, 1, 5], [0, 5, 4],  # -y
            [3, 6, 2], [3, 7, 6],  # +y
            [0, 7, 3], [0, 4, 7],  # -x
            [1, 2, 6], [1, 6, 5],  # +x
        ],
        np.int32,
    )
    return vertices, faces


def make_icosphere(subdivisions: int = 2) -> tuple[np.ndarray, np.ndarray]:
    """Unit icosphere (radius 0.5) via icosahedron midpoint subdivision."""
    phi = (1.0 + np.sqrt(5.0)) / 2.0
    raw = np.array(
        [
            [-1, phi, 0], [1, phi, 0], [-1, -phi, 0], [1, -phi, 0],
            [0, -1, phi], [0, 1, phi], [0, -1, -phi], [0, 1, -phi],
            [phi, 0, -1], [phi, 0, 1], [-phi, 0, -1], [-phi, 0, 1],
        ],
        np.float32,
    )
    vertices = raw / np.linalg.norm(raw, axis=1, keepdims=True)
    faces = np.array(
        [
            [0, 11, 5], [0, 5, 1], [0, 1, 7], [0, 7, 10], [0, 10, 11],
            [1, 5, 9], [5, 11, 4], [11, 10, 2], [10, 7, 6], [7, 1, 8],
            [3, 9, 4], [3, 4, 2], [3, 2, 6], [3, 6, 8], [3, 8, 9],
            [4, 9, 5], [2, 4, 11], [6, 2, 10], [8, 6, 7], [9, 8, 1],
        ],
        np.int32,
    )
    for _ in range(subdivisions):
        midpoint_cache: dict[tuple[int, int], int] = {}
        vertex_list = [v for v in vertices]
        new_faces = []

        def midpoint(a: int, b: int) -> int:
            key = (min(a, b), max(a, b))
            if key not in midpoint_cache:
                m = vertex_list[a] + vertex_list[b]
                m = m / np.linalg.norm(m)
                midpoint_cache[key] = len(vertex_list)
                vertex_list.append(m.astype(np.float32))
            return midpoint_cache[key]

        for f in faces:
            a, b, c = int(f[0]), int(f[1]), int(f[2])
            ab, bc, ca = midpoint(a, b), midpoint(b, c), midpoint(c, a)
            new_faces += [[a, ab, ca], [b, bc, ab], [c, ca, bc], [ab, bc, ca]]
        vertices = np.stack(vertex_list)
        faces = np.array(new_faces, np.int32)
    return (vertices * 0.5).astype(np.float32), faces


# The scanned-model stand-in (scene family 03_physics-2-scan): 660 x 660
# quads = 871,200 triangles, the count of the Stanford 3D Scanning
# Repository's dragon_vrip.ply (871,414). The geometry seed is a constant
# of the family, as for every procedural scene here.
SCAN_GRID = 660
SCAN_SEED = 871414
SCAN_MAJOR_RADIUS = 0.26
SCAN_TUBE_RADIUS = 0.16
SCAN_NOISE_AMPLITUDE = 0.075  # 15% of the bounding radius 0.5
SCAN_OCTAVES = 5


class ScanModel(NamedTuple):
    """One generated stand-in for a scanned model: ``make_scan_mesh``'s
    arguments, and what it stands for."""

    stands_for: str
    published_triangles: int
    grid: int
    seed: int
    tube_over_major: float | None


# The bodies of scene family 03_physics-2-assets: three different meshes
# at the triangle counts of the Stanford 3D Scanning Repository's models
# (no network here, so each is generated; the seed is the published count,
# the proportions differ so that the models differ in shape). Body ``i`` is
# an instance of model ``i mod 3``; "dragon" is the scan family's mesh to
# the byte. The program is general in the number of models; the list stops
# at the dragon because a benchmark run builds the set three times and
# checks it against a plain reference inside a time limit (PERF.md §7: with
# the happy buddha's 1,087,716 as a fourth model a run did not fit it), and
# Thai statue (10,000,000) and Lucy (28,055,742) are 39 million triangles
# more, 2.5 GB of slabs: their wide tops would fit (``TOP_VMEM_BUDGET``), the
# run's time limit would not.
ASSET_MODELS: dict[str, ScanModel] = {
    "bunny": ScanModel("bun_zipper.ply", 69_451, 186, 69_451, 0.9),
    "armadillo": ScanModel("Armadillo.ply", 345_944, 416, 345_944, 0.45),
    "dragon": ScanModel("dragon_vrip.ply", 871_414, SCAN_GRID, SCAN_SEED, None),
}


def make_scan_mesh(
    grid: int = SCAN_GRID, seed: int = SCAN_SEED,
    tube_over_major: float | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """A closed, non-convex surface of ``2 * grid * grid`` triangles that
    stands for a scanned model: a doubly periodic ``grid x grid`` net of
    quads wrapped on a ring (axis z, so it stands on the floor like a
    wheel inside the unit box the other meshes fill), each quad split
    along a seeded diagonal, the surface pushed along the tube's normal
    by ``SCAN_OCTAVES`` octaves of seeded periodic noise (amplitudes
    halving, summing to ``SCAN_NOISE_AMPLITUDE``) and every vertex moved
    inside its cell, so triangle areas are irregular. ``tube_over_major``
    is the ring's proportions, the tube's radius over the ring's, at the
    same reach (their sum); None is the scan family's 0.16 over 0.26."""
    major, tube_radius = SCAN_MAJOR_RADIUS, SCAN_TUBE_RADIUS
    if tube_over_major is not None:
        major = (SCAN_MAJOR_RADIUS + SCAN_TUBE_RADIUS) / (1.0 + tube_over_major)
        tube_radius = major * tube_over_major
    rng = np.random.default_rng(seed)
    iu, iv = np.meshgrid(np.arange(grid), np.arange(grid), indexing="ij")
    two_pi = 2.0 * np.pi
    u = (iu + (rng.random((grid, grid)) - 0.5) * 0.7) * (two_pi / grid)
    v = (iv + (rng.random((grid, grid)) - 0.5) * 0.7) * (two_pi / grid)
    weights = 0.5 ** np.arange(SCAN_OCTAVES)
    weights *= SCAN_NOISE_AMPLITUDE / weights.sum()
    waves = 3
    displacement = np.zeros((grid, grid))
    for octave, weight in enumerate(weights):
        for _ in range(waves):
            # whole-number frequencies keep the noise periodic in u and v
            fu, fv = rng.integers(-3, 4, size=2) * (1 << octave)
            if fu == 0 and fv == 0:
                fu = 1 << octave
            displacement += (weight / waves) * np.cos(
                fu * u + fv * v + rng.random() * two_pi
            )
    tube = tube_radius + displacement
    ring = major + tube * np.cos(v)
    vertices = np.stack(
        [ring * np.cos(u), ring * np.sin(u), tube * np.sin(v)], axis=-1
    ).reshape(-1, 3).astype(np.float32)
    a = (iu * grid + iv).reshape(-1)
    b = (((iu + 1) % grid) * grid + iv).reshape(-1)
    c = (((iu + 1) % grid) * grid + (iv + 1) % grid).reshape(-1)
    d = (iu * grid + (iv + 1) % grid).reshape(-1)
    flip = rng.random(a.shape[0]) < 0.5
    first = np.stack([a, b, np.where(flip, d, c)], axis=1)
    second = np.stack([np.where(flip, b, a), c, d], axis=1)
    faces = np.stack([first, second], axis=1).reshape(-1, 3).astype(np.int32)
    return vertices, faces


# ---------------------------------------------------------------------------
# Host-side BVH build (numpy — runs once per mesh, cached)


def _half_area(lo: np.ndarray, hi: np.ndarray) -> float:
    """Half surface area of an AABB — the SAH's relative cost weight."""
    e = np.maximum(hi - lo, 0.0)
    return float(e[0] * e[1] + e[1] * e[2] + e[2] * e[0])


SAH_BINS = 16


def _sah_partition(
    tri: np.ndarray, centroids: np.ndarray, indices: np.ndarray
) -> tuple[np.ndarray, np.ndarray] | None:
    """Binned-SAH split of ``indices``: minimize area_L*n_L + area_R*n_R
    over SAH_BINS centroid bins on each axis. Returns (left, right) index
    arrays or None when no axis admits a non-degenerate split (the caller
    falls back to the median split, which always makes progress)."""
    c = centroids[indices]
    pts = tri[indices]  # [n, 3, 3] — axis-independent, gathered once
    best = None  # (cost, axis, threshold-bin, bin ids)
    for axis in range(3):
        lo = float(c[:, axis].min())
        hi = float(c[:, axis].max())
        if hi - lo < 1e-12:
            continue
        bins = np.clip(
            ((c[:, axis] - lo) / (hi - lo) * SAH_BINS).astype(np.int64),
            0, SAH_BINS - 1,
        )
        counts = np.bincount(bins, minlength=SAH_BINS)
        # Per-bin bounds over the member triangles' vertices.
        bin_lo = np.full((SAH_BINS, 3), np.inf)
        bin_hi = np.full((SAH_BINS, 3), -np.inf)
        for b in range(SAH_BINS):
            member = bins == b
            if member.any():
                p = pts[member].reshape(-1, 3)
                bin_lo[b] = p.min(axis=0)
                bin_hi[b] = p.max(axis=0)
        # Prefix/suffix sweep: split "after bin b" for b in [0, SAH_BINS-2].
        lo_acc, hi_acc = np.full(3, np.inf), np.full(3, -np.inf)
        left_area = np.zeros(SAH_BINS)
        left_count = np.cumsum(counts)
        for b in range(SAH_BINS):
            lo_acc = np.minimum(lo_acc, bin_lo[b])
            hi_acc = np.maximum(hi_acc, bin_hi[b])
            left_area[b] = _half_area(lo_acc, hi_acc)
        lo_acc, hi_acc = np.full(3, np.inf), np.full(3, -np.inf)
        right_area = np.zeros(SAH_BINS)
        for b in range(SAH_BINS - 1, 0, -1):
            lo_acc = np.minimum(lo_acc, bin_lo[b])
            hi_acc = np.maximum(hi_acc, bin_hi[b])
            right_area[b - 1] = _half_area(lo_acc, hi_acc)
        right_count = left_count[-1] - left_count  # tris in bins > b
        for b in range(SAH_BINS - 1):
            if left_count[b] == 0 or right_count[b] == 0:
                continue
            cost = (
                left_area[b] * left_count[b] + right_area[b] * right_count[b]
            )
            if best is None or cost < best[0]:
                best = (cost, axis, b, bins)
    if best is None:
        return None
    _, axis, threshold, bins = best
    # Split at the SAH bin boundary. (A leaf-aligned variant that snaps
    # the split count to multiples of LEAF_SIZE was tried — 20 perfectly
    # full leaves instead of 26 — and measured SLOWER on the deep scene:
    # the snapped planes make leaf boxes fat enough that extra packet
    # visits outweigh the saved leaf tests. Spatial tightness wins.)
    left = indices[bins <= threshold]
    right = indices[bins > threshold]
    return left, right


def build_bvh(
    vertices: np.ndarray,
    faces: np.ndarray,
    builder: str = "median",
    wide: int = 1,
    treelet_leaves: int | None = None,
) -> MeshBVH:
    """Host-side BLAS build, threaded for stackless traversal.

    ``builder="morton"`` is the vectorised build for meshes the recursive
    ones below would take minutes over (``morton_bvh``: binary, ``wide``
    does not apply); ``treelet_leaves`` is its treelet size where the
    caller wants the streamed tables whatever the mesh's size.

    ``builder`` selects the split strategy — ``median`` (the original
    spatial-median over centroids) or ``sah`` (binned surface-area
    heuristic: better-fitting subtrees and fuller leaves, so traversal
    visits fewer nodes). ``wide`` > 1 collapses the binary tree into an
    N-ary one by pulling grandchildren up (largest-area inner child
    first): the intermediate binary levels disappear, so the threaded
    skip-link walk — which is arity-agnostic — steps through ~half the
    inner nodes for the same leaves. Both knobs change only the ARRAY
    CONTENTS of the MeshBVH, never the traversal contract, so every
    kernel variant consumes any build unchanged.
    """
    leaf_size = LEAF_SIZE
    wide = max(1, min(int(wide), 8))
    if builder == "morton":
        return morton_bvh(vertices, faces, treelet_leaves=treelet_leaves)
    if builder not in ("median", "sah"):
        raise ValueError(f"Unknown BVH builder: {builder!r}")
    tri = vertices[faces]  # [T, 3, 3]
    centroids = tri.mean(axis=1)
    order = np.arange(len(faces))

    # Recursive build producing (bounds, leaf range | child list).
    nodes: list[dict] = []

    def emit(indices: np.ndarray) -> int:
        node_index = len(nodes)
        pts = tri[indices].reshape(-1, 3)
        node = {
            "min": pts.min(axis=0),
            "max": pts.max(axis=0),
            "first": -1,
            "count": 0,
            "children": None,
        }
        nodes.append(node)
        if len(indices) <= leaf_size:
            node["first"] = indices  # placeholder; flattened below
            node["count"] = len(indices)
            return node_index
        part = None
        if builder == "sah":
            split = _sah_partition(tri, centroids, indices)
            if split is not None:
                part = split
        if part is None:
            # Median split (the only strategy guaranteed to make progress
            # on degenerate all-equal-centroid sets).
            extent = (
                centroids[indices].max(axis=0) - centroids[indices].min(axis=0)
            )
            axis = int(np.argmax(extent))
            mid = len(indices) // 2
            ordered = indices[
                np.argsort(centroids[indices, axis], kind="stable")
            ]
            part = (ordered[:mid], ordered[mid:])
        left = emit(part[0])
        right = emit(part[1])
        node["children"] = [left, right]
        return node_index

    emit(order)

    if wide > 1:
        # Collapse to N-ary: repeatedly replace the largest-area inner
        # child with its own children (in place, preserving order) until
        # the node has ``wide`` children or only leaves remain. Collapsed
        # inner nodes are dropped at flatten time (unreachable).
        def widen(i: int) -> None:
            node = nodes[i]
            if node["children"] is None:
                return
            children = list(node["children"])
            while len(children) < wide:
                inner = [
                    c for c in children if nodes[c]["children"] is not None
                ]
                if not inner:
                    break
                pick = max(
                    inner,
                    key=lambda c: _half_area(nodes[c]["min"], nodes[c]["max"]),
                )
                at = children.index(pick)
                children[at:at + 1] = nodes[pick]["children"]
            node["children"] = children
            for c in children:
                widen(c)

        widen(0)
        # Re-emit reachable nodes in DFS preorder (drops collapsed ones).
        remap: list[dict] = []

        def reindex(i: int) -> int:
            node = nodes[i]
            new_index = len(remap)
            remap.append(node)
            if node["children"] is not None:
                node["children"] = [reindex(c) for c in node["children"]]
            return new_index

        reindex(0)
        nodes = remap

    # Flatten leaves into aligned LEAF_SIZE-wide slots (-1 = degenerate pad).
    tri_order: list[int] = []
    first = np.zeros(len(nodes), np.int32)
    count = np.zeros(len(nodes), np.int32)
    for i, node in enumerate(nodes):
        if node["children"] is None:
            first[i] = len(tri_order)
            count[i] = node["count"]
            members = [int(t) for t in node["first"]]
            tri_order.extend(members + [-1] * (LEAF_SIZE - len(members)))

    # Skip links: nodes are already in DFS preorder (emit order); a node's
    # skip is the next node that is NOT in its subtree. Compute subtree
    # sizes by walking children (any arity).
    subtree = np.ones(len(nodes), np.int32)

    def size(i: int) -> int:
        node = nodes[i]
        if node["children"] is not None:
            subtree[i] = 1 + sum(size(c) for c in node["children"])
        return subtree[i]

    size(0)
    skip = np.array([i + subtree[i] for i in range(len(nodes))], np.int32)

    # Octant-ordered re-threadings (sah builds): eight DFS orders of the
    # SAME tree, children sorted near-first along each octant's sign
    # vector. Subtree sizes are order-invariant, so the local skip link
    # at position p is simply p + subtree[node]. Leaf slots are shared
    # with the canonical order — only node rows move.
    octant_tables = None
    if builder == "sah":
        centers = [0.5 * (nd["min"] + nd["max"]) for nd in nodes]
        ob_min, ob_max = [], []
        o_skip, o_first, o_count = [], [], []
        for octant in range(8):
            sgn = np.array(
                [
                    1.0 if octant & 1 else -1.0,
                    1.0 if octant & 2 else -1.0,
                    1.0 if octant & 4 else -1.0,
                ]
            )
            order: list[int] = []

            def emit_octant(i: int) -> None:
                order.append(i)
                ch = nodes[i]["children"]
                if ch is None:
                    return
                for c in sorted(
                    ch, key=lambda c: float(centers[c] @ sgn)
                ):
                    emit_octant(c)

            emit_octant(0)
            ob_min.append(np.stack([nodes[i]["min"] for i in order]))
            ob_max.append(np.stack([nodes[i]["max"] for i in order]))
            o_skip.append(
                np.array(
                    [p + subtree[i] for p, i in enumerate(order)], np.int32
                )
            )
            o_first.append(first[order])
            o_count.append(count[order])

    order_array = np.array(tri_order, np.int64)
    real = order_array >= 0
    reordered = np.zeros((len(order_array), 3, 3), np.float32)
    reordered[real] = tri[order_array[real]]  # pad rows stay all-zero
    v0 = reordered[:, 0]
    e1 = reordered[:, 1] - reordered[:, 0]
    e2 = reordered[:, 2] - reordered[:, 0]
    n = np.cross(e1, e2)
    norm = np.linalg.norm(n, axis=1, keepdims=True)
    n = np.where(norm > 1e-12, n / np.maximum(norm, 1e-12), np.array([[0.0, 1.0, 0.0]], np.float32))
    # ensure_compile_time_eval: the first build may happen INSIDE a jit
    # trace (fused_frame_renderer -> scene_mesh_set -> cached_mesh_bvh),
    # where bare jnp.asarray would return trace-local tracers — which the
    # lru_cache would then hand to later EAGER callers (render_frame,
    # the sharded renderer) as leaked tracers. This forces concrete, cache-safe arrays
    # regardless of the first caller's context.
    with jax.ensure_compile_time_eval():
        if octant_tables is None and builder == "sah":
            octant_tables = OctantTables(
                bounds_min=jnp.asarray(
                    np.concatenate(ob_min).astype(np.float32)
                ),
                bounds_max=jnp.asarray(
                    np.concatenate(ob_max).astype(np.float32)
                ),
                skip=jnp.asarray(np.concatenate(o_skip)),
                first=jnp.asarray(np.concatenate(o_first)),
                count=jnp.asarray(np.concatenate(o_count)),
            )
        return MeshBVH(
            v0=jnp.asarray(v0),
            e1=jnp.asarray(e1),
            e2=jnp.asarray(e2),
            normal=jnp.asarray(n.astype(np.float32)),
            bounds_min=jnp.asarray(np.stack([nd["min"] for nd in nodes])),
            bounds_max=jnp.asarray(np.stack([nd["max"] for nd in nodes])),
            skip=jnp.asarray(skip),
            first=jnp.asarray(first),
            count=jnp.asarray(count),
            octant=octant_tables,
        )


# ---------------------------------------------------------------------------
# Vectorised build (Morton order, balanced tree) and the treelet partition


def _morton_codes(centroids: np.ndarray) -> np.ndarray:
    """63-bit Morton codes of ``centroids`` (21 bits an axis)."""
    lo = centroids.min(axis=0)
    span = np.maximum(centroids.max(axis=0) - lo, 1e-12)
    cells = np.minimum(
        ((centroids - lo) / span * (1 << 21)).astype(np.uint64), (1 << 21) - 1
    )

    def dilate(x):
        x = (x | (x << np.uint64(32))) & np.uint64(0x1F00000000FFFF)
        x = (x | (x << np.uint64(16))) & np.uint64(0x1F0000FF0000FF)
        x = (x | (x << np.uint64(8))) & np.uint64(0x100F00F00F00F00F)
        x = (x | (x << np.uint64(4))) & np.uint64(0x10C30C30C30C30C3)
        x = (x | (x << np.uint64(2))) & np.uint64(0x1249249249249249)
        return x

    return (
        dilate(cells[:, 0])
        | (dilate(cells[:, 1]) << np.uint64(1))
        | (dilate(cells[:, 2]) << np.uint64(2))
    )


def _balanced_tree(n_leaves: int):
    """The median-split binary tree over ``n_leaves`` ordered leaves, in
    DFS preorder, level by level (no Python per node): a node over ``m``
    leaves holds ``2m - 1`` nodes, its left child follows it and its right
    child follows the left subtree. Returns (leaf range lo, hi per node,
    [(parents, lefts, rights)] per level from the root down)."""
    n_nodes = 2 * n_leaves - 1
    node_lo = np.zeros(n_nodes, np.int64)
    node_hi = np.zeros(n_nodes, np.int64)
    index = np.zeros(1, np.int64)
    lo = np.zeros(1, np.int64)
    hi = np.full(1, n_leaves, np.int64)
    levels = []
    while index.size:
        node_lo[index], node_hi[index] = lo, hi
        inner = hi - lo > 1
        index, lo, hi = index[inner], lo[inner], hi[inner]
        mid = (lo + hi) // 2
        left, right = index + 1, index + 2 * (mid - lo)
        levels.append((index, left, right))
        index = np.concatenate([left, right])
        lo, hi = np.concatenate([lo, mid]), np.concatenate([mid, hi])
    return node_lo, node_hi, levels


def _build_bvh_morton(vertices: np.ndarray, faces: np.ndarray) -> dict:
    """The vectorised BLAS build behind ``build_bvh(builder="morton")``:
    triangles sorted along a Morton curve through their centroids, every
    ``LEAF_SIZE`` consecutive ones a leaf, a balanced median-split tree
    over the leaves, bounds reduced bottom-up a level at a time. Host
    arrays under ``MeshBVH``'s field names (threaded DFS preorder,
    leaf-contiguous triangles), plus the leaf range of every node."""
    tri = np.asarray(vertices, np.float32)[np.asarray(faces)]  # [T, 3, 3]
    tri = tri[np.argsort(_morton_codes(tri.mean(axis=1, dtype=np.float64)), kind="stable")]
    n_tri = tri.shape[0]
    n_leaves = -(-n_tri // LEAF_SIZE)
    padded = np.zeros((n_leaves * LEAF_SIZE, 3, 3), np.float32)
    padded[:n_tri] = tri
    leaf_count = np.full(n_leaves, LEAF_SIZE, np.int32)
    leaf_count[-1] = n_tri - (n_leaves - 1) * LEAF_SIZE
    # Bounds of a leaf over its real triangles: the padding repeats the
    # leaf's first vertex for the reduction only.
    points = padded.copy()
    points[n_tri:] = padded[(n_leaves - 1) * LEAF_SIZE, 0]
    points = points.reshape(n_leaves, LEAF_SIZE * 3, 3)
    node_lo, node_hi, levels = _balanced_tree(n_leaves)
    n_nodes = node_lo.shape[0]
    is_leaf = node_hi - node_lo == 1
    bounds_min = np.zeros((n_nodes, 3), np.float32)
    bounds_max = np.zeros((n_nodes, 3), np.float32)
    bounds_min[is_leaf] = points.min(axis=1)[node_lo[is_leaf]]
    bounds_max[is_leaf] = points.max(axis=1)[node_lo[is_leaf]]
    for parents, lefts, rights in reversed(levels):
        bounds_min[parents] = np.minimum(bounds_min[lefts], bounds_min[rights])
        bounds_max[parents] = np.maximum(bounds_max[lefts], bounds_max[rights])
    e1 = padded[:, 1] - padded[:, 0]
    e2 = padded[:, 2] - padded[:, 0]
    normal = np.cross(e1, e2)
    length = np.linalg.norm(normal, axis=1, keepdims=True)
    normal = np.where(
        length > 1e-12, normal / np.maximum(length, 1e-12),
        np.array([[0.0, 1.0, 0.0]], np.float32),
    ).astype(np.float32)
    return dict(
        v0=np.ascontiguousarray(padded[:, 0]), e1=e1, e2=e2, normal=normal,
        bounds_min=bounds_min, bounds_max=bounds_max,
        skip=(np.arange(n_nodes) + 2 * (node_hi - node_lo) - 1).astype(np.int32),
        first=np.where(is_leaf, node_lo * LEAF_SIZE, 0).astype(np.int32),
        count=np.where(is_leaf, leaf_count[np.minimum(node_lo, n_leaves - 1)], 0).astype(np.int32),
        leaf_lo=node_lo, leaf_hi=node_hi,
    )


# What one treelet may hold, in leaves: 64 leaves are 1,024 triangles,
# 64 KiB of triangle rows and 4 KiB of wide nodes a fetch.
TREELET_LEAVES = 64
# A BLAS stays resident (today's kernels: triangle tables whole in VMEM,
# node tables in SMEM) while its tables fit this share of the 16 MiB of
# VMEM a kernel may use by default; a larger one is streamed.
RESIDENT_VMEM_BUDGET = 8 << 20


def resident_table_bytes(n_triangle_rows: int, n_nodes: int) -> int:
    """VMEM + SMEM bytes of a BLAS as the resident kernels hold it: four
    ``[T, 3]`` f32 tables whose rows pad to 128 lanes, and 9 words a
    node."""
    return n_triangle_rows * 128 * 4 * 4 + n_nodes * 9 * 4


def _first_fitting(lo: np.ndarray, hi: np.ndarray, most: int) -> np.ndarray:
    """The nodes of a preorder tree that hold at most ``most`` leaves and
    whose parent holds more. In preorder a node's parent is the nearest
    earlier node whose range holds it, so a fitting node is the first on
    its path iff it is not inside the preorder span of an earlier fitting
    node."""
    index = np.arange(lo.shape[0])
    fits = hi - lo <= most
    span_end = np.where(fits, index + 2 * (hi - lo) - 1, 0)
    covered_until = np.maximum.accumulate(np.concatenate([[0], span_end[:-1]]))
    return fits & (index >= covered_until)


# Levels of wide nodes a tree's top may have: the walk keeps one (wide
# node, bits left) a level in a stack of this depth. Six levels reach
# treelet roots 18 binary levels down, 262,144 treelets a model, which is
# more than twice what the top's bytes allow (``TOP_VMEM_BUDGET``).
TOP_LEVELS = 6
# Wide nodes of one ``[8, 128]`` tile of boxes, eight lanes each.
TILE_NODES = 128 // 8


def _wide_top(tree: dict, is_root: np.ndarray, treelet: np.ndarray):
    """The tree above its treelet roots (``is_root``) as wide nodes, a
    level of them at a time: a wide node stands for a binary node, and its
    children are what three binary levels below that node hold, a treelet
    root standing where it is met. The model's root alone takes one or two
    levels where the deepest treelet root's depth is no multiple of three,
    so the odd levels cost one narrow node at the top, not a narrow node
    above every pair of treelets. A child's slot is its path's bits, so
    the slots run in preorder, and a slot under a treelet root met sooner
    is empty (inverted box, bit 0). Wide nodes are numbered in the
    preorder of their binary nodes. Returns (boxes ``[NW, child, 8]``:
    lo xyz, hi xyz, the child's bit ``1 << c``, a spare; links ``[NW * 8]``
    int32: ``-1 - v`` for wide node ``v``, ``treelet + 1``, 0 for none)."""
    skip = tree["skip"]

    def below(nodes):
        """(left, right) of each of ``nodes``, a treelet root or a
        missing one (-1) standing for itself beside a missing one."""
        node = np.maximum(nodes, 0)
        inner = (nodes >= 0) & ~is_root[node]
        return (
            np.where(inner, nodes + 1, nodes),
            np.where(inner, skip[np.minimum(node + 1, skip.size - 1)], -1),
        )

    depth, level = 0, np.zeros(1, np.int64)  # of the deepest treelet root
    while not is_root[level].all():
        level = np.concatenate(below(level))
        level, depth = level[level >= 0], depth + 1
    heads, slots = [], []
    level = np.zeros(1, np.int64)
    while level.size:
        if len(heads) == TOP_LEVELS:
            raise ValueError(
                f"a resident top deeper than {TOP_LEVELS} levels of wide "
                "nodes outgrows the walk's stack"
            )
        children = np.full((level.size, WIDE), -1, np.int64)
        children[:, 0] = level
        levels = 3 if heads else (depth - 1) % 3 + 1
        for stride in (4, 2, 1)[3 - levels:]:
            left, right = below(children[:, ::2 * stride])
            children[:, ::2 * stride], children[:, stride::2 * stride] = left, right
        heads.append(level)
        slots.append(children)
        level = children[(children >= 0) & ~is_root[np.maximum(children, 0)]]
    heads, slots = np.concatenate(heads), np.concatenate(slots)
    order = np.argsort(heads)
    heads, slots = heads[order], slots[order]
    held = slots >= 0
    node = np.maximum(slots, 0)
    links = np.where(
        held,
        np.where(is_root[node], treelet[node] + 1, -1 - np.searchsorted(heads, node)),
        0,
    ).astype(np.int32)
    boxes = np.zeros((heads.size, WIDE, 8), np.float32)
    boxes[..., 0:3] = np.where(held[..., None], tree["bounds_min"][node], INF)
    boxes[..., 3:6] = np.where(held[..., None], tree["bounds_max"][node], -INF)
    boxes[..., 6] = np.where(held, 1 << np.arange(WIDE), 0)
    return boxes, links.reshape(-1)


def partition_treelets(tree: dict, max_leaves: int = TREELET_LEAVES) -> dict:
    """Cut a ``_build_bvh_morton`` tree into treelets of at most
    ``max_leaves`` leaves (8, 16, 32 or 64: a byte budget of
    ``max_leaves`` KiB of triangle rows): host arrays under
    ``BlasStream``'s field names, the top's boxes still a wide node a row
    (``blas_stream`` tiles them). A treelet root is a node that fits the
    budget whose parent does not; what lies above is the resident top
    (``_wide_top``). Inside a treelet the same rule at ``WIDE`` leaves
    finds its groups: the balanced tree has at most ``max_leaves / WIDE``
    of them, which become the children of the treelet's wide root, and a
    group's leaves the children of its wide node."""
    if max_leaves not in (8, 16, 32, 64):
        raise ValueError(f"treelet size {max_leaves}: want 8, 16, 32 or 64")
    lo, hi = tree["leaf_lo"], tree["leaf_hi"]
    is_root = _first_fitting(lo, hi, max_leaves)
    is_group = _first_fitting(lo, hi, WIDE)
    n_treelets = int(is_root.sum())
    treelet = np.cumsum(is_root) - 1  # of a root, and of every node under it
    top_boxes, top_links = _wide_top(tree, is_root, treelet)

    # Wide nodes. Groups and leaves come in preorder, which is left to
    # right: a group's place under its root and a leaf's under its group
    # are the binary walk's order of meeting them.
    groups = np.flatnonzero(is_group)
    group_treelet = treelet[groups]
    group_child = np.arange(groups.shape[0]) - np.searchsorted(group_treelet, group_treelet)
    leaves = np.flatnonzero(tree["count"] > 0)
    leaf_group = (np.cumsum(is_group) - 1)[leaves]
    leaf_child = lo[leaves] - lo[groups[leaf_group]]
    assert group_child.max() < max_leaves // WIDE and leaf_child.max() < WIDE
    # [treelet, child, wide node, 8 words]: an empty slot's box is
    # inverted and its bit 0, so no mask holds it.
    wide = np.zeros((n_treelets, WIDE, TILE_NODES, 8), np.float32)
    wide[..., 0:3], wide[..., 3:6] = INF, -INF
    for treelets, child, node, members in (
        (group_treelet, group_child, 0, groups),
        (treelet[leaves], leaf_child, 1 + group_child[leaf_group], leaves),
    ):
        wide[treelets, child, node, 0:3] = tree["bounds_min"][members]
        wide[treelets, child, node, 3:6] = tree["bounds_max"][members]
        wide[treelets, child, node, 6] = 1 << child
    # Triangle rows: [treelet, leaf slot, triangle, 16 lanes] -> leaves
    # eight abreast across the 128 lanes; a group is a block of 16 rows.
    n_leaves = int(hi[0])
    record = np.zeros((n_leaves * LEAF_SIZE, 16), np.float32)
    for column, name in enumerate(("v0", "e1", "e2", "normal")):
        record[:, 3 * column:3 * column + 3] = tree[name]
    record = record.reshape(n_leaves, LEAF_SIZE, 16)
    slots = np.zeros((n_treelets, max_leaves, LEAF_SIZE, 16), np.float32)
    slots[treelet[leaves], WIDE * group_child[leaf_group] + leaf_child] = record[lo[leaves]]
    tri = slots.reshape(n_treelets, max_leaves // 8, 8, LEAF_SIZE, 16).transpose(
        0, 1, 3, 2, 4
    ).reshape(n_treelets, max_leaves * 2, 128)
    return dict(
        tri=np.concatenate([tri, wide.reshape(n_treelets, WIDE, 128)], axis=1),
        top_boxes=top_boxes, top_links=top_links,
        root=np.stack([tree["bounds_min"][0], tree["bounds_max"][0]])[None],
        top_first=np.array([0, top_boxes.shape[0]], np.int32),
    )


def join_treelet_tables(models: list[dict]) -> dict:
    """Several ``partition_treelets`` results as one set of tables: the
    slabs and the tops end to end, a later model's links and treelet
    numbers moved past the earlier models' wide nodes and slabs. The
    tables of one model come back as they are."""
    nodes = np.cumsum([0] + [m["top_links"].shape[0] // WIDE for m in models])
    slabs = np.cumsum([0] + [m["tri"].shape[0] for m in models])
    if len({m["tri"].shape[1] for m in models}) != 1:
        raise ValueError("the BLASes of one set share one treelet size")
    top_links = np.concatenate([
        m["top_links"] + np.where(
            m["top_links"] > 0, slab, np.where(m["top_links"] < 0, -node, 0)
        ).astype(np.int32)
        for m, node, slab in zip(models, nodes, slabs)
    ])
    return dict(
        tri=np.concatenate([m["tri"] for m in models]),
        top_boxes=np.concatenate([m["top_boxes"] for m in models]),
        top_links=top_links,
        root=np.concatenate([m["root"] for m in models]),
        top_first=nodes.astype(np.int32),
    )


# What the boxes of a scene's resident tops may take of VMEM while a bounce
# kernel runs, at 8 x 8 words a wide node: 16,384 wide nodes, about 114,000
# treelets, 117 million triangles (7.9 GB of slabs, half the chip's HBM).
# Their links, 8 words a node, are then 512 KiB of a core's 1 MiB of SMEM.
# Tried at the compiler: a launch over a top of this size compiles
# (tests/test_bringup.py), and so does one of 28,672 wide nodes (7 MiB of
# boxes, 896 KiB of links); at 32,768 the links and the kernel's other
# scalar operands are 1.06 MiB and the launch is refused for SMEM. So the
# budget is half of what the compiler was seen to take, and past it the
# links bind before the boxes do.
TOP_VMEM_BUDGET = 4 << 20
TOP_NODE_BYTES = WIDE * 8 * 4


def blas_stream(tables: dict) -> BlasStream:
    """``partition_treelets``' or ``join_treelet_tables``' host tables as
    the bounce kernel's operands on the device. Every streamed build
    passes here, so here a top past ``TOP_VMEM_BUDGET`` is refused and
    the top's ``[NW, child, 8 words]`` boxes are laid in ``[8, 128]``
    tiles of sixteen (``BlasStream.top_boxes``): child down the sublanes,
    wide node ``w`` at lanes ``8 (w % 16) ..`` of tile ``w // 16``, the
    last tile's spare nodes empty slots (inverted boxes, no bit)."""
    boxes = tables["top_boxes"]
    if boxes.shape[0] * TOP_NODE_BYTES > TOP_VMEM_BUDGET:
        raise ValueError(
            f"a resident top of {boxes.shape[0]} wide nodes "
            f"({boxes.shape[0] * TOP_NODE_BYTES} B of boxes) does not fit "
            f"its share of VMEM ({TOP_VMEM_BUDGET} B)"
        )
    tiles = -(-boxes.shape[0] // TILE_NODES)
    padded = np.zeros((tiles * TILE_NODES, WIDE, 8), np.float32)
    padded[..., 0:3], padded[..., 3:6] = INF, -INF
    padded[:boxes.shape[0]] = boxes
    tiled = padded.reshape(tiles, TILE_NODES, WIDE, 8).transpose(
        0, 2, 1, 3
    ).reshape(tiles * WIDE, 128)
    return BlasStream(**{
        name: jnp.asarray(value)
        for name, value in {**tables, "top_boxes": tiled}.items()
    })


def morton_bvh(
    vertices: np.ndarray, faces: np.ndarray, *,
    treelet_leaves: int | None = None,
) -> MeshBVH:
    """``_build_bvh_morton``'s tree as a ``MeshBVH``. Resident or streamed
    follows from the tables' bytes against ``RESIDENT_VMEM_BUDGET``:
    a tree that fits comes back as device arrays like every other build;
    one that does not keeps its arrays on the host and carries the
    treelet tables (``BlasStream``) on the device. ``treelet_leaves``
    asks for the streamed tables beside device arrays whatever the size
    (tests walk one tree both ways)."""
    tree = _build_bvh_morton(vertices, faces)
    fields = {name: tree[name] for name in MeshBVH._fields[:9]}
    streamed = _is_streamed(tree)
    with jax.ensure_compile_time_eval():  # see build_bvh
        stream = None
        if streamed or treelet_leaves is not None:
            tables = partition_treelets(tree, treelet_leaves or TREELET_LEAVES)
            stream = blas_stream(tables)
        if not streamed:
            fields = {name: jnp.asarray(value) for name, value in fields.items()}
        return MeshBVH(**fields, stream=stream)


def _is_streamed(tree: dict) -> bool:
    return resident_table_bytes(
        tree["v0"].shape[0], tree["skip"].shape[0]
    ) > RESIDENT_VMEM_BUDGET


def morton_bvh_set(
    meshes: dict, *, treelet_leaves: int | None = None, built=None,
) -> MeshBVH:
    """Several meshes' BLASes as ONE set the bounce kernel streams: each
    mesh of ``meshes`` (by name, a callable that makes its vertices and
    faces, so only one mesh's working arrays live at a time) is built by
    ``_build_bvh_morton`` and cut by ``partition_treelets`` on its own,
    the tables are joined on the host (``join_treelet_tables``) and put on
    the device once. The triangle arrays are the models' end to end
    (host), ``tri_first`` says where each begins, ``bounds_min`` /
    ``bounds_max`` hold each model's root box, and the set has no node
    arrays of its own. A set is streamed whole: a mesh small enough to be
    resident is refused (one resident BLAS runs the resident kernels, and
    there is no third path for a mix). ``built(name, triangles, began,
    seconds)`` hears of each mesh as its tables are done, and of the
    join and the copy to the device as ``"upload"``."""
    trees, tables = [], []
    for name, make in meshes.items():
        began, started = time.time(), time.perf_counter()
        vertices, faces = make()
        tree = _build_bvh_morton(vertices, faces)
        if not _is_streamed(tree) and treelet_leaves is None:
            raise ValueError(
                f"mesh {name!r} of the set ({faces.shape[0]} triangles) fits "
                "the resident budget: a scene's BLASes are one resident "
                "BLAS or all streamed, not a mix"
            )
        tables.append(partition_treelets(tree, treelet_leaves or TREELET_LEAVES))
        trees.append({f: tree[f] for f in ("v0", "e1", "e2", "normal")})
        if built is not None:
            built(name, faces.shape[0], began, time.perf_counter() - started)
    began, started = time.time(), time.perf_counter()
    joined = join_treelet_tables(tables)
    del tables
    triangles = {f: np.concatenate([t[f] for t in trees]) for f in trees[0]}
    with jax.ensure_compile_time_eval():  # see build_bvh
        bvh = MeshBVH(
            **triangles,
            bounds_min=joined["root"][:, 0], bounds_max=joined["root"][:, 1],
            skip=None, first=None, count=None,
            stream=blas_stream(joined),
            tri_first=np.cumsum(
                [0] + [t["v0"].shape[0] for t in trees]
            ).astype(np.int32),
        )
        jax.block_until_ready(bvh.stream)
    if built is not None:
        built("upload", 0, began, time.perf_counter() - started)
    return bvh


def traced_stream_bvh(stream: BlasStream) -> MeshBVH:
    """The ``MeshBVH`` a traced program holds of its streamed BLASes: the
    treelet tables (its arguments) and each model's root box (row ``m`` of
    ``bounds_min`` / ``bounds_max``), which is all the instance table
    takes of a tree; the host arrays stay out of the program."""
    return MeshBVH(
        v0=None, e1=None, e2=None, normal=None,
        bounds_min=stream.root[:, 0], bounds_max=stream.root[:, 1],
        skip=None, first=None, count=None, stream=stream,
    )


# Process-wide geometry-build memo: host-side BVH/TLAS builds keyed by
# every parameter that shapes the result — (kind, leaf_size) for BLAS
# builds, (k_count, tlas_leaf_size) for TLAS topologies — so the test
# suite and a renderer rebuilt for another shape never rebuild a
# hierarchy they have already built this process. An explicit dict (not
# lru_cache) so tests can reset it: tests/conftest.py wires
# ``reset_geometry_cache`` into its autouse fixture.
_geometry_cache: dict[tuple, object] = {}


def reset_geometry_cache() -> None:
    """Forget memoized host-side BVH/TLAS builds (test isolation only:
    the builds are pure, so resetting merely makes the next call rebuild
    — per-test build-count assertions stay independent of earlier
    tests)."""
    _geometry_cache.clear()


def bvh_builder() -> str:
    """``TRC_BVH_BUILDER``: ``sah`` (default, binned SAH) or ``median``.

    A static-jit-arg env tier: read by the UNTRACED drivers/factories and
    threaded into build keys and kernel identities — never read inside a
    traced function (the ``env-tiers`` lint pass pins this), so toggling
    it mid-process builds a fresh tree instead of serving a stale one.
    """
    from tpu_render_cluster.utils.env import env_str

    value = (env_str("TRC_BVH_BUILDER") or "sah").strip().lower()
    return value if value in ("sah", "median") else "sah"


def bvh_wide() -> int:
    """``TRC_BVH_WIDE``: BLAS branching factor after the wide collapse
    (default 4; 1 = binary; clamped to [1, 8]). Same static-jit-arg
    contract as ``bvh_builder``."""
    from tpu_render_cluster.utils.env import env_int

    return max(1, min(env_int("TRC_BVH_WIDE", 4), 8))


def cached_mesh_bvh(
    kind: str, builder: str | None = None, wide: int | None = None, *,
    built=None,
) -> MeshBVH:
    """Memoized BLAS build. The key carries EVERY build parameter —
    (kind, leaf size, builder, wide arity) — so flipping
    ``TRC_BVH_BUILDER``/``TRC_BVH_WIDE`` mid-process can never serve a
    tree built under the old knobs. ``None`` resolves the env tiers
    (callers inside traced code must pass explicit values).
    ``built(model, triangles, began, seconds)`` hears of each BLAS this
    call builds (``morton_bvh_set``'s for a set; none where the build is
    found cached)."""
    if kind in ("scan", "assets"):
        builder, wide = "morton", 1  # their one build: the env tiers do not apply
    builder = bvh_builder() if builder is None else builder
    wide = bvh_wide() if wide is None else max(1, min(int(wide), 8))
    key = ("bvh", kind, LEAF_SIZE, builder, wide)
    bvh = _geometry_cache.get(key)
    if bvh is None:
        began, started = time.time(), time.perf_counter()
        if kind == "assets":
            bvh = morton_bvh_set(
                {
                    name: functools.partial(
                        make_scan_mesh, model.grid, model.seed, model.tube_over_major
                    )
                    for name, model in ASSET_MODELS.items()
                },
                built=built,
            )
        else:
            if kind == "box":
                vertices, faces = make_box()
            elif kind == "icosphere":
                vertices, faces = make_icosphere(2)
            elif kind == "scan":
                vertices, faces = make_scan_mesh()
            else:
                raise ValueError(f"Unknown mesh kind: {kind!r}")
            bvh = build_bvh(vertices, faces, builder=builder, wide=wide)
            # the tables are on the device, not on their way
            jax.block_until_ready(bvh)
            if built is not None:
                built(kind, faces.shape[0], began, time.perf_counter() - started)
        _geometry_cache[key] = bvh
    return bvh


# ---------------------------------------------------------------------------
# Intersection


def _moller_trumbore(origins, directions, v0, e1, e2):
    """Batched ray x triangle test: [R, T] hit distances (INF = miss)."""
    # pvec = d x e2; det = e1 . pvec  (per ray-triangle pair)
    pvec = jnp.cross(directions[:, None, :], e2[None, :, :])
    det = jnp.sum(e1[None, :, :] * pvec, axis=-1)
    inv_det = 1.0 / jnp.where(jnp.abs(det) < 1e-12, 1e-12, det)
    tvec = origins[:, None, :] - v0[None, :, :]
    u = jnp.sum(tvec * pvec, axis=-1) * inv_det
    qvec = jnp.cross(tvec, e1[None, :, :])
    v = jnp.sum(directions[:, None, :] * qvec, axis=-1) * inv_det
    t = jnp.sum(e2[None, :, :] * qvec, axis=-1) * inv_det
    hit = (
        (jnp.abs(det) > 1e-12)
        & (u >= 0.0)
        & (v >= 0.0)
        & (u + v <= 1.0)
        & (t > EPS)
    )
    return jnp.where(hit, t, INF)


def intersect_triangles_brute(bvh: MeshBVH, origins, directions):
    """Nearest triangle hit by brute force — the correctness reference.

    Returns (t [R], triangle_index [R] int32).
    """
    t = _moller_trumbore(origins, directions, bvh.v0, bvh.e1, bvh.e2)
    best = jnp.argmin(t, axis=-1).astype(jnp.int32)
    return jnp.take_along_axis(t, best[:, None], axis=-1)[:, 0], best


def intersect_bvh_packet(bvh: MeshBVH, origins, directions, init_t=None):
    """Threaded-BVH packet traversal in pure XLA (runs on any platform).

    One node walk is shared by the whole ray packet: the scalar walk index
    advances on the block-wide ``any`` of the per-ray AABB tests. Returns
    (t [R], triangle_index [R] int32) identical to the brute-force result.

    ``init_t`` seeds the per-ray cull distance (e.g. the nearest hit found
    on previously-scanned instances), letting the walk prune subtrees that
    cannot beat an existing hit.
    """
    n_nodes = bvh.skip.shape[0]
    inv_dir = 1.0 / jnp.where(
        jnp.abs(directions) < 1e-12, jnp.where(directions < 0, -1e-12, 1e-12),
        directions,
    )

    def aabb_any_hit(node, best_t):
        lo = (bvh.bounds_min[node][None, :] - origins) * inv_dir
        hi = (bvh.bounds_max[node][None, :] - origins) * inv_dir
        tmin = jnp.max(jnp.minimum(lo, hi), axis=-1)
        tmax = jnp.min(jnp.maximum(lo, hi), axis=-1)
        hit = (tmax >= jnp.maximum(tmin, 0.0)) & (tmin < best_t)
        return jnp.any(hit)

    def leaf_intersect(node, best_t, best_index):
        start = bvh.first[node]
        v0 = jax.lax.dynamic_slice(bvh.v0, (start, 0), (LEAF_SIZE, 3))
        e1 = jax.lax.dynamic_slice(bvh.e1, (start, 0), (LEAF_SIZE, 3))
        e2 = jax.lax.dynamic_slice(bvh.e2, (start, 0), (LEAF_SIZE, 3))
        t = _moller_trumbore(origins, directions, v0, e1, e2)  # [R, LEAF_SIZE]
        in_leaf = jnp.arange(LEAF_SIZE)[None, :] < bvh.count[node]
        t = jnp.where(in_leaf, t, INF)
        local = jnp.argmin(t, axis=-1)
        t_leaf = jnp.take_along_axis(t, local[:, None], axis=-1)[:, 0]
        closer = t_leaf < best_t
        best_t = jnp.where(closer, t_leaf, best_t)
        best_index = jnp.where(
            closer, (start + local).astype(jnp.int32), best_index
        )
        return best_t, best_index

    def cond(carry):
        node, _, _ = carry
        return node < n_nodes

    def body(carry):
        node, best_t, best_index = carry
        hit_any = aabb_any_hit(node, best_t)
        is_leaf = bvh.count[node] > 0

        def on_hit(args):
            best_t, best_index = args

            def leaf(args):
                return leaf_intersect(node, *args)

            best_t, best_index = jax.lax.cond(
                is_leaf, leaf, lambda args: args, (best_t, best_index)
            )
            next_node = jnp.where(is_leaf, bvh.skip[node], node + 1)
            return next_node, best_t, best_index

        def on_miss(args):
            best_t, best_index = args
            return bvh.skip[node], best_t, best_index

        return jax.lax.cond(hit_any, on_hit, on_miss, (best_t, best_index))

    r = origins.shape[0]
    start_t = (
        jnp.full((r,), INF, jnp.float32) if init_t is None else init_t
    )
    init = (jnp.int32(0), start_t, jnp.zeros((r,), jnp.int32))
    _, best_t, best_index = jax.lax.while_loop(cond, body, init)
    return best_t, best_index


def occluded_bvh_packet(bvh: MeshBVH, origins, directions, already) -> jnp.ndarray:
    """Any-hit packet walk: True per ray once ANY triangle is hit.

    ``already`` marks rays occluded by earlier instances — they stop
    driving traversal (pruning whole subtrees), with no nearest-hit
    ordering or argmin bookkeeping. Deliberately NO data-dependent early
    exit of the walk itself: a per-step all() reduce costs more on TPU
    than the node visits it saves (measured -6% on the mesh bench).
    """
    n_nodes = bvh.skip.shape[0]
    inv_dir = 1.0 / jnp.where(
        jnp.abs(directions) < 1e-12, jnp.where(directions < 0, -1e-12, 1e-12),
        directions,
    )

    def cond(carry):
        node, _ = carry
        return node < n_nodes

    def body(carry):
        node, occluded = carry
        lo = (bvh.bounds_min[node][None, :] - origins) * inv_dir
        hi = (bvh.bounds_max[node][None, :] - origins) * inv_dir
        tmin = jnp.max(jnp.minimum(lo, hi), axis=-1)
        tmax = jnp.min(jnp.maximum(lo, hi), axis=-1)
        packet_hit = (tmax >= jnp.maximum(tmin, 0.0)) & ~occluded
        hit_any = jnp.any(packet_hit)
        is_leaf = bvh.count[node] > 0

        def on_leaf(occluded):
            start = bvh.first[node]
            v0 = jax.lax.dynamic_slice(bvh.v0, (start, 0), (LEAF_SIZE, 3))
            e1 = jax.lax.dynamic_slice(bvh.e1, (start, 0), (LEAF_SIZE, 3))
            e2 = jax.lax.dynamic_slice(bvh.e2, (start, 0), (LEAF_SIZE, 3))
            t = _moller_trumbore(origins, directions, v0, e1, e2)
            in_leaf = jnp.arange(LEAF_SIZE)[None, :] < bvh.count[node]
            return occluded | jnp.any(jnp.where(in_leaf, t, INF) < INF, axis=-1)

        def on_hit(occluded):
            occluded = jax.lax.cond(
                is_leaf, on_leaf, lambda occluded: occluded, occluded
            )
            return jnp.where(is_leaf, bvh.skip[node], node + 1), occluded

        def on_miss(occluded):
            return bvh.skip[node], occluded

        return jax.lax.cond(hit_any, on_hit, on_miss, occluded)

    _, occluded = jax.lax.while_loop(
        cond, body, (jnp.int32(0), already)
    )
    return occluded


# ---------------------------------------------------------------------------
# Instances


class MeshInstances(NamedTuple):
    """K similarity-transformed instances of object-space meshes.

    ``x_world = scale * rotation @ x_obj + translation``. Rays are pulled
    back with the inverse; dividing BOTH the local origin and direction by
    ``scale`` preserves the ray parameter t, so per-instance hits compare
    directly in world units and one static BVH serves every animated
    instance of a model.

    ``model`` says which BLAS of the scene's set an instance is (None:
    the one there is); ``tri_first`` / ``tri_count`` are that model's rows
    of the set's triangle arrays, filled in by ``scene_mesh_set`` for
    whoever takes the geometry as plain arrays (the benchmark's
    references) and read by no traced program.
    """

    rotation: jnp.ndarray  # [K, 3, 3] pure rotations
    translation: jnp.ndarray  # [K, 3]
    albedo: jnp.ndarray  # [K, 3]
    scale: jnp.ndarray  # [K] uniform per-instance scale
    model: "jnp.ndarray | None" = None  # [K] int32
    tri_first: "np.ndarray | None" = None  # [K] int32
    tri_count: "np.ndarray | None" = None  # [K] int32


def _rays_to_object_space(instances: MeshInstances, k, origins, directions):
    """World -> object: x' = R^T (x - t) / s; the direction is scaled by
    1/s too, which keeps the ray parameter t in world units.

    The rotation is applied elementwise (the 3-wide contraction unrolled):
    it stays on the VPU in full f32 — precision="highest" einsum forces a
    slow multi-pass MXU lowering, while the default bf16 matmul path puts
    ~0.4% relative error on ray origins (centimeters at scene scale).
    """
    rot = instances.rotation[k]
    inv_scale = 1.0 / instances.scale[k]
    shifted = origins - instances.translation[k][None, :]
    local_origins = (
        shifted[:, 0:1] * rot[0][None, :]
        + shifted[:, 1:2] * rot[1][None, :]
        + shifted[:, 2:3] * rot[2][None, :]
    ) * inv_scale
    local_directions = (
        directions[:, 0:1] * rot[0][None, :]
        + directions[:, 1:2] * rot[1][None, :]
        + directions[:, 2:3] * rot[2][None, :]
    ) * inv_scale
    return local_origins, local_directions


def _normals_to_world(rot, normal_obj):
    """World normal = R n_obj (rigid: inverse transpose == R).

    ``rot`` may be one [3, 3] rotation or a per-ray [R, 3, 3] batch.
    Unrolled elementwise so it stays on the VPU in full f32: the default
    matmul precision rounds through bf16 and visibly tilts shading normals
    (~0.2%).
    """
    return (
        rot[..., :, 0] * normal_obj[:, 0:1]
        + rot[..., :, 1] * normal_obj[:, 1:2]
        + rot[..., :, 2] * normal_obj[:, 2:3]
    )


def intersect_instances(
    bvh: MeshBVH, instances: MeshInstances, origins, directions, init_t=None
):
    """Nearest hit over all instances.

    Returns (t [R], normal [R, 3] world-space, albedo [R, 3]). Rigid
    transforms preserve ray parameter t, so per-instance results compare
    directly. ``init_t`` (optional, [R]) seeds the best-t with a hit the
    caller already knows (the same bounce's sphere/plane t): lanes whose
    seed beats an instance's AABB entry stop driving that instance's walk,
    and a mesh miss returns t == init_t (never closer, so callers using a
    strict ``<`` comparison see it as a miss).

    A lax.scan of per-instance XLA walks (``intersect_bvh_packet``): the
    reference path, what ``trace_paths`` runs where the Pallas kernels
    are off, and what every kernel is tested against.
    """

    def per_instance(carry, k):
        best_t, best_normal, best_albedo = carry
        rot = instances.rotation[k]
        local_origins, local_directions = _rays_to_object_space(
            instances, k, origins, directions
        )
        # Seed the walk with the best hit so far: t is in world units for
        # every instance, so earlier instances' hits prune this walk.
        t, tri = intersect_bvh_packet(
            bvh, local_origins, local_directions, best_t
        )
        normal_obj = bvh.normal[tri]
        normal_world = _normals_to_world(rot, normal_obj)
        closer = t < best_t
        best_t = jnp.where(closer, t, best_t)
        best_normal = jnp.where(closer[:, None], normal_world, best_normal)
        best_albedo = jnp.where(
            closer[:, None], instances.albedo[k][None, :], best_albedo
        )
        return (best_t, best_normal, best_albedo), None

    r = origins.shape[0]
    init = (
        jnp.full((r,), INF, jnp.float32) if init_t is None else init_t,
        jnp.zeros((r, 3), jnp.float32),
        jnp.zeros((r, 3), jnp.float32),
    )
    k_count = instances.translation.shape[0]
    (best_t, best_normal, best_albedo), _ = jax.lax.scan(
        per_instance, init, jnp.arange(k_count)
    )
    # Flip normals to face the incoming ray.
    facing = jnp.sum(best_normal * directions, axis=-1) < 0.0
    best_normal = jnp.where(facing[:, None], best_normal, -best_normal)
    return best_t, best_normal, best_albedo


def occluded_instances(
    bvh: MeshBVH, instances: MeshInstances, origins, directions, already=None
):
    """Any-hit over all instances (shadow rays).

    Cheaper than ``intersect_instances``: shadow rays only need a boolean,
    so the per-instance scan skips the normal/albedo gathers and transform.
    ``already`` (optional, [R] bool) marks lanes the caller already knows
    are occluded (e.g. by the sphere any-hit): they stop driving the walks
    and come back True. A lax.scan of XLA walks (``occluded_bvh_packet``):
    the reference path, like ``intersect_instances``.
    """
    if already is None:
        already = jnp.zeros((origins.shape[0],), bool)

    def per_instance(occluded, k):
        local_origins, local_directions = _rays_to_object_space(
            instances, k, origins, directions
        )
        occluded = occluded_bvh_packet(
            bvh, local_origins, local_directions, occluded
        )
        return occluded, None

    k_count = instances.translation.shape[0]
    occluded, _ = jax.lax.scan(
        per_instance,
        already,
        jnp.arange(k_count),
    )
    return occluded


def rotation_y(angle):
    """[..., 3, 3] rotation about +y for scalar or batched angles."""
    c, s = jnp.cos(angle), jnp.sin(angle)
    zero, one = jnp.zeros_like(c), jnp.ones_like(c)
    return jnp.stack(
        [
            jnp.stack([c, zero, s], axis=-1),
            jnp.stack([zero, one, zero], axis=-1),
            jnp.stack([-s, zero, c], axis=-1),
        ],
        axis=-2,
    )


# ---------------------------------------------------------------------------
# Two-level hierarchy: TLAS over instances (ISSUE 10)
#
# The flat in-kernel instance sweep visits every instance's world AABB per
# ray block; the TLAS replaces that with a threaded skip-link walk over a
# small tree of instance groups, so a block only descends into the
# subtrees its packet actually overlaps. Split of responsibilities under
# jit: instance transforms are TRACED (physics animation), so the tree
# TOPOLOGY must be frame-invariant — it is a median split over instance
# SLOTS (static numpy, memoized per (k_count, leaf_size)), while the
# slot -> instance assignment (a Morton sort of world-AABB centers) and
# the per-node bounds (segment unions over the sorted AABBs) are cheap
# XLA arithmetic recomputed per frame. A Morton-sorted median split is a
# spatial-median build — the SAH sweep of a classic host build needs
# data-dependent topology, which a jitted per-frame build cannot have.


class TlasTopology(NamedTuple):
    """Static (numpy) threaded TLAS topology over ``k_count`` instance
    slots: DFS preorder, skip links, leaves covering contiguous slot
    ranges. ``member`` is the [M, K] node->slot incidence mask the
    per-frame bounds reduction uses.

    ``octant_*`` are the eight near-first re-threadings (octant o at
    rows [o*M, (o+1)*M), LOCAL skip links, ``octant_perm`` mapping each
    row to its canonical node for the per-frame bounds gather): slots
    are Morton-ordered, so a median split at depth d cuts the curve's
    most-significant live axis — z, y, x cycling — and visiting the low
    half first is near-first for positive direction components along
    that axis. A heuristic order (any order is exact); the sah-build
    kernels walk the table matching each packet's direction octant.
    """

    skip: np.ndarray  # [M] int32 — next subtree root (M = done)
    first: np.ndarray  # [M] int32 — leaf slot start (0 for inner)
    count: np.ndarray  # [M] int32 — leaf slot count (0 for inner)
    member: np.ndarray  # [M, K] bool — node covers instance slot
    depth: int  # tree depth (root = 1)
    octant_skip: np.ndarray  # [8M] int32 — LOCAL skip links per octant
    octant_first: np.ndarray  # [8M] int32
    octant_count: np.ndarray  # [8M] int32
    octant_perm: np.ndarray  # [8M] int32 — row -> canonical node index


def build_tlas_topology(k_count: int, leaf_size: int) -> TlasTopology:
    """Median split over instance slot ranges, threaded like build_bvh."""
    if k_count < 1:
        raise ValueError("TLAS needs at least one instance")
    leaf_size = max(1, leaf_size)
    nodes: list[dict] = []

    def emit(lo: int, hi: int, level: int) -> tuple[int, int]:
        node_index = len(nodes)
        nodes.append(
            {"lo": lo, "hi": hi, "leaf": hi - lo <= leaf_size,
             "level": level, "children": None}
        )
        if nodes[node_index]["leaf"]:
            return node_index, level
        mid = (lo + hi) // 2
        left, left_depth = emit(lo, mid, level + 1)
        right, right_depth = emit(mid, hi, level + 1)
        nodes[node_index]["children"] = (left, right)
        return node_index, max(left_depth, right_depth)

    _, depth = emit(0, k_count, 1)
    m = len(nodes)
    # DFS preorder by construction; a node's subtree is the consecutive
    # run of nodes whose slot range nests inside its own.
    skip = np.zeros(m, np.int32)
    first = np.zeros(m, np.int32)
    count = np.zeros(m, np.int32)
    member = np.zeros((m, k_count), bool)
    for i, node in enumerate(nodes):
        j = i + 1
        while j < m and nodes[j]["lo"] >= node["lo"] and nodes[j]["hi"] <= node["hi"]:
            j += 1
        skip[i] = j
        member[i, node["lo"]:node["hi"]] = True
        if node["leaf"]:
            first[i] = node["lo"]
            count[i] = node["hi"] - node["lo"]
    subtree = skip - np.arange(m, dtype=np.int32)
    octant_skip = np.zeros(8 * m, np.int32)
    octant_first = np.zeros(8 * m, np.int32)
    octant_count = np.zeros(8 * m, np.int32)
    octant_perm = np.zeros(8 * m, np.int32)
    for octant in range(8):
        order: list[int] = []

        def emit_octant(i: int) -> None:
            order.append(i)
            children = nodes[i]["children"]
            if children is None:
                return
            # Morton MSB cycle: depth 1 splits z, then y, then x.
            axis = (2, 1, 0)[(nodes[i]["level"] - 1) % 3]
            low_first = bool(octant & (1 << axis))
            left, right = children
            emit_octant(left if low_first else right)
            emit_octant(right if low_first else left)

        emit_octant(0)
        base = octant * m
        for position, i in enumerate(order):
            octant_skip[base + position] = position + subtree[i]
            octant_first[base + position] = first[i]
            octant_count[base + position] = count[i]
            octant_perm[base + position] = i
    return TlasTopology(
        skip=skip, first=first, count=count, member=member, depth=depth,
        octant_skip=octant_skip, octant_first=octant_first,
        octant_count=octant_count, octant_perm=octant_perm,
    )


def tlas_build_counter(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.counter(
        "render_tlas_builds_total",
        "Host-side TLAS topology builds (cache misses of the process-wide "
        "geometry memo — bounded by distinct (instance count, leaf size) "
        "pairs, never frames)",
    )


def tlas_depth_gauge(registry=None):
    from tpu_render_cluster.obs import get_registry

    registry = registry if registry is not None else get_registry()
    return registry.gauge(
        "render_tlas_depth",
        "Depth of the most recently built TLAS topology (root = 1)",
    )


def cached_tlas_topology(k_count: int, leaf_size: int) -> TlasTopology:
    """Memoized ``build_tlas_topology`` (see ``_geometry_cache``)."""
    key = ("tlas", k_count, leaf_size)
    topology = _geometry_cache.get(key)
    if topology is None:
        topology = build_tlas_topology(k_count, leaf_size)
        _geometry_cache[key] = topology
        tlas_build_counter().inc()
        tlas_depth_gauge().set(topology.depth)
    return topology


def tlas_node_bounds(topology: TlasTopology, lo_sorted, hi_sorted):
    """Per-frame TLAS node AABBs from SORTED instance world AABBs.

    ``lo_sorted``/``hi_sorted`` are [K, 3] in slot order (the Morton
    permutation applied). Returns ([M, 3], [M, 3]) node unions — pure
    masked min/max off the static incidence mask, so it jits/vmaps.
    """
    mask = jnp.asarray(topology.member)[:, :, None]  # [M, K, 1]
    node_lo = jnp.min(jnp.where(mask, lo_sorted[None], INF), axis=1)
    node_hi = jnp.max(jnp.where(mask, hi_sorted[None], -INF), axis=1)
    return node_lo, node_hi


# ---------------------------------------------------------------------------
# Quantized node tables (ISSUE 15): fixed-point AABB slabs + packed meta
#
# A walk reads its node table once a visit, so node bytes are what it
# moves; this compresses a node table from 36 B/node (6 f32 slabs + 3 int32 links)
# to 16 B (quant tier 1: 16-bit slabs packed two-per-int32 word) or 12 B
# (tier 2: 8-bit slabs packed six-per-two-words), with skip/first/count
# folded into ONE int32 meta word. Quantization is against the table's own
# union AABB with CONSERVATIVE outward rounding — a reconstructed box always
# CONTAINS its fp32 original (floor/ceil to the grid plus a pad absorbing
# f32 reconstruction rounding), so a quantized walk visits a superset of
# the exact walk's nodes and, because best-t updates compare exact triangle
# hits with a strict <, produces bit-identical results. One jnp
# implementation serves both the static BLAS (constant-folded under jit)
# and the per-frame traced TLAS bounds; tests/test_bvhq.py pins the
# containment property on randomized and degenerate inputs.

# Meta word layout (LSB->MSB): skip [0:16), first/first_unit [16:27),
# count [27:32). Ranges are shape-checkable, so the drivers degrade to the
# unquantized format when a table outgrows them (pallas_kernels.
# resolve_bvh_quant).
QUANT_MAX_NODES = 1 << 16
QUANT_MAX_FIRST_UNITS = 1 << 11
QUANT_MAX_COUNT = 31
# Outward pad in grid cells per tier: guarantees the f32 reconstruction
# (origin + q * cell, the kernels' exact arithmetic) stays outside the
# original bounds even under worst-case rounding of the quantize divide
# and the reconstruction multiply-add (the grid window is padded so one
# cell is never smaller than ~1 ulp of the coordinate scale).
_QUANT_PAD = {1: 4, 2: 1}
_QUANT_BITS = {1: 16, 2: 8}


def quantize_node_tables(lo, hi, skip, first, count, *, quant: int,
                         first_unit: int):
    """Pack a threaded node table into its quantized form.

    ``lo``/``hi`` [N, 3] node AABBs (traced or static), ``skip``/
    ``first``/``count`` [N] int32 links, ``first_unit`` the alignment of
    ``first`` (LEAF_SIZE for BLAS tables, 1 for TLAS slot ranges).
    Returns ``(bq [N, 3|2] int32, meta [N] int32, grid [6] f32)`` where
    ``grid`` = (origin[3], cell[3]) and a slab reconstructs as
    ``origin + q * cell`` (see ``dequantize_node_bounds``).
    """
    bits = _QUANT_BITS[quant]
    levels = (1 << bits) - 1
    pad = _QUANT_PAD[quant]
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    glo = jnp.min(lo, axis=0)
    ghi = jnp.max(hi, axis=0)
    # Window pad: keeps one grid cell >= ~30 ulp of the coordinate scale
    # even for degenerate (flat / single-point) tables, so the per-node
    # cell pad above really is an outward margin after f32 rounding.
    eps = (jnp.abs(glo) + jnp.abs(ghi) + 1.0) * 2e-3
    origin = glo - eps
    cell = ((ghi + eps) - origin) / levels
    inv = 1.0 / cell
    qlo = jnp.clip(
        jnp.floor((lo - origin) * inv).astype(jnp.int32) - pad, 0, levels
    )
    qhi = jnp.clip(
        jnp.ceil((hi - origin) * inv).astype(jnp.int32) + pad, 0, levels
    )
    if quant == 1:
        bq = qlo | (qhi << 16)  # [N, 3]: per-axis (lo | hi << 16)
    else:
        w0 = (
            qlo[:, 0] | (qlo[:, 1] << 8) | (qlo[:, 2] << 16)
            | (qhi[:, 0] << 24)
        )
        w1 = qhi[:, 1] | (qhi[:, 2] << 8)
        bq = jnp.stack([w0, w1], axis=1)  # [N, 2]
    skip = jnp.asarray(skip, jnp.int32)
    first = jnp.asarray(first, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    meta = skip | ((first // first_unit) << 16) | (count << 27)
    grid = jnp.concatenate([origin, cell])
    return bq, meta, grid


def dequantize_node_bounds(bq, grid, quant: int):
    """XLA twin of the kernels' scalar slab reconstruction — THE one
    arithmetic (``origin + q * cell`` in f32) the containment property is
    asserted against. Returns ([N, 3] lo, [N, 3] hi)."""
    if quant == 1:
        qlo = bq & 0xFFFF
        qhi = (bq >> 16) & 0xFFFF
    else:
        qlo = jnp.stack(
            [bq[:, 0] & 0xFF, (bq[:, 0] >> 8) & 0xFF,
             (bq[:, 0] >> 16) & 0xFF],
            axis=1,
        )
        qhi = jnp.stack(
            [(bq[:, 0] >> 24) & 0xFF, bq[:, 1] & 0xFF,
             (bq[:, 1] >> 8) & 0xFF],
            axis=1,
        )
    origin, cell = grid[None, 0:3], grid[None, 3:6]
    return (
        origin + qlo.astype(jnp.float32) * cell,
        origin + qhi.astype(jnp.float32) * cell,
    )


def unpack_node_meta(meta, *, first_unit: int):
    """XLA twin of the kernels' meta-word unpack: (skip, first, count)."""
    skip = meta & 0xFFFF
    first = ((meta >> 16) & 0x7FF) * first_unit
    count = (meta >> 27) & 0x1F
    return skip, first, count


def morton_dilate5(v):
    """Spread the low 5 bits of a uint32 to every 3rd position (Morton
    dilation) — THE shared definition for the coherence-key quantization
    (instance slot assignment here, the kernels' fused sort-key epilogue
    and its XLA twin in pallas_kernels)."""
    v = (v | (v << 8)) & jnp.uint32(0x0300F)
    v = (v | (v << 4)) & jnp.uint32(0x030C3)
    v = (v | (v << 2)) & jnp.uint32(0x09249)
    return v


def instance_morton_order(lo_w, hi_w):
    """Morton order of instance world-AABB centers ([K] int32 permutation).

    The TLAS slot assignment: spatially-adjacent instances land in the
    same leaves, so subtree unions stay tight. Ray-INDEPENDENT by design
    (unlike the flat path's near-first anchor sort): a region launch and
    the whole-frame launch derive identical instance orders, keeping the
    tiled-equals-untiled contracts exact. Stable argsort, so equal codes
    (e.g. the degenerate all-overlapping field) keep their original
    relative order.
    """
    centers = 0.5 * (lo_w + hi_w)  # [K, 3]
    lo = jnp.min(centers, axis=0)
    span = jnp.maximum(jnp.max(centers, axis=0) - lo, 1e-6)
    cell = jnp.clip(
        (centers - lo) / span * 32.0, 0.0, 31.0
    ).astype(jnp.uint32)
    code = (
        morton_dilate5(cell[:, 0])
        | (morton_dilate5(cell[:, 1]) << 1)
        | (morton_dilate5(cell[:, 2]) << 2)
    )
    return jnp.argsort(code).astype(jnp.int32)


class MeshSet(NamedTuple):
    """A mesh-backed scene's geometry: its BLAS, or its set of BLASes in
    one ``MeshBVH`` (``morton_bvh_set``), + the instances, each of which
    names its model."""

    bvh: MeshBVH
    instances: MeshInstances


def scene_mesh_set(
    scene_name: str, frame, builder: str | None = None,
    wide: int | None = None, stream: "BlasStream | None" = None,
) -> "MeshSet | None":
    """The MeshSet for a scene (None for sphere-only scenes).

    The BVH is a cached constant (host-built once); only the instance
    transforms depend on the frame, so this composes into jit/vmap.
    ``builder``/``wide`` select the BLAS build (None = env tiers); the
    jitted renderer factories resolve them OUTSIDE the trace and pass
    explicit values, so the compiled program's tree matches its cache
    key. ``stream`` is a streamed BLAS's tables where the caller is a
    traced program that takes them as arguments (``scene_blas_stream``):
    the set then holds those and none of the tree's host arrays.
    """
    from tpu_render_cluster.render.scene import (
        build_mesh_instances,
        mesh_kind_for_scene,
    )

    kind = mesh_kind_for_scene(scene_name)
    if kind is None:
        return None
    instances = build_mesh_instances(scene_name, frame)
    if stream is not None:
        return MeshSet(traced_stream_bvh(stream), instances)
    bvh = cached_mesh_bvh(kind, builder, wide)
    first = (
        np.array([0, bvh.v0.shape[0]], np.int32) if bvh.tri_first is None
        else bvh.tri_first
    )
    model = np.asarray(instances.model)  # the family's rule: never traced
    return MeshSet(bvh, instances._replace(
        tri_first=first[model], tri_count=first[model + 1] - first[model],
    ))


def scene_blas_stream(
    scene_name: str, builder: str | None = None, wide: int | None = None
) -> "BlasStream | None":
    """The HBM tables of a scene whose BLAS is streamed, None for every
    other scene. The renderer factories call this outside their traces
    and hand the tables to the frame's program as arguments: 70 MB
    closed over would be 70 MB of constants in the compiled program."""
    from tpu_render_cluster.render.scene import mesh_kind_for_scene

    kind = mesh_kind_for_scene(scene_name)
    return None if kind is None else cached_mesh_bvh(kind, builder, wide).stream


# NOTE: an instance-flattened variant (one K*R-ray traversal call instead
# of a K-step lax.scan) was tried and measured SLOWER on TPU at render ray
# counts (8.9 vs 9.6 f/s): the per-instance grids already fill the device,
# and materializing [K*R, 3] local-ray buffers multiplies HBM traffic by
# K. The scan keeps live buffers at [R, 3] and additionally benefits from
# cross-instance best_t cull seeding.
