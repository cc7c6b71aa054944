"""A BLAS too large for VMEM is streamed from HBM by treelet: the scene
family ``03_physics-2-scan`` (ISSUE 32).

Small and seeded: a 2,048-triangle scan mesh (``make_scan_mesh(grid=32)``),
streaming forced by a small treelet budget, the Pallas interpreter on the
CPU. What is held:

- the vectorised build keeps the invariants the recursive one's tests
  assert, and its walk finds what brute force finds;
- the treelets partition the tree;
- the streamed walk gives the resident walk's bounce bit for bit over the
  same tree (state out, radiance, sort keys), and both give brute force's
  hit: the distance and the triangle through the new origin (hit point
  pushed along the triangle's normal), the instance through the throughput
  (its albedo), the any-hit through the sun term;
- the family's frame agrees with the benchmark's independent reference
  (``plain_tracer_accel``) by the check's own rule, and that reference agrees
  with ``plain_tracer`` where both can trace;
- resident or streamed follows from the tables' bytes, and the icosphere
  scene's program holds the kernels it held.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

SCAN_SCENE = "03_physics-2-scan"
SMALL_GRID = 32  # 2 * 32 * 32 = 2,048 triangles
SMALL_TREELET = 8


@pytest.fixture
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("TRC_PALLAS", "1")


@pytest.fixture
def small_scan_family(monkeypatch, interpreted_kernels):
    """The scan family over the 2,048-triangle mesh, streamed: the mesh's
    size, the VMEM budget and the treelet size are the module's constants,
    so a test changes them there and nowhere in the program."""
    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render import mesh as mesh_module

    monkeypatch.setattr(
        mesh_module, "make_scan_mesh",
        functools.partial(mesh_module.make_scan_mesh, grid=SMALL_GRID),
    )
    monkeypatch.setattr(mesh_module, "RESIDENT_VMEM_BUDGET", 0)
    monkeypatch.setattr(mesh_module, "TREELET_LEAVES", SMALL_TREELET)
    mesh_module.reset_geometry_cache()
    integrator.fused_frame_renderer.cache_clear()
    integrator.fused_region_renderer.cache_clear()
    yield mesh_module
    mesh_module.reset_geometry_cache()
    integrator.fused_frame_renderer.cache_clear()
    integrator.fused_region_renderer.cache_clear()


def small_tree(treelet_leaves=None):
    from tpu_render_cluster.render import mesh as mesh_module

    vertices, faces = mesh_module.make_scan_mesh(grid=SMALL_GRID)
    return mesh_module.build_bvh(
        vertices, faces, builder="morton", treelet_leaves=treelet_leaves
    ), faces


# -- the build -------------------------------------------------------------------


def test_the_scan_mesh_is_closed_seeded_and_irregular():
    from tpu_render_cluster.render.mesh import make_scan_mesh

    vertices, faces = make_scan_mesh(grid=SMALL_GRID)
    again, _ = make_scan_mesh(grid=SMALL_GRID)
    assert faces.shape == (2 * SMALL_GRID * SMALL_GRID, 3)
    np.testing.assert_array_equal(vertices, again)
    edges = np.sort(np.concatenate([faces[:, [0, 1]], faces[:, [1, 2]], faces[:, [2, 0]]]), axis=1)
    _, shared = np.unique(edges, axis=0, return_counts=True)
    assert (shared == 2).all(), "every edge belongs to two triangles: a closed surface"
    corners = vertices[faces]
    areas = 0.5 * np.linalg.norm(np.cross(corners[:, 1] - corners[:, 0], corners[:, 2] - corners[:, 0]), axis=1)
    assert areas.min() > 0 and areas.max() > 3 * np.median(areas)
    assert np.abs(vertices).max() <= 0.5  # inside the unit box the other meshes fill


@pytest.mark.parametrize("source", ["scan", "box", "icosphere"])
def test_the_vectorised_build_keeps_the_recursive_builds_invariants(source):
    from tpu_render_cluster.render import mesh as mesh_module

    if source == "scan":
        vertices, faces = mesh_module.make_scan_mesh(grid=SMALL_GRID)
    else:
        vertices, faces = mesh_module.make_box() if source == "box" else mesh_module.make_icosphere(2)
    bvh = mesh_module.build_bvh(vertices, faces, builder="morton")
    n_nodes = bvh.skip.shape[0]
    skip, count, first = (np.asarray(a) for a in (bvh.skip, bvh.count, bvh.first))
    assert (skip > np.arange(n_nodes)).all() and (skip <= n_nodes).all() and skip[0] == n_nodes
    leaves = count > 0
    assert (first[leaves] % mesh_module.LEAF_SIZE == 0).all()
    assert (count[leaves] <= mesh_module.LEAF_SIZE).all()
    assert bvh.v0.shape[0] % mesh_module.LEAF_SIZE == 0
    assert int(count.sum()) == len(faces)
    assert sorted(first[leaves]) == list(range(0, bvh.v0.shape[0], mesh_module.LEAF_SIZE))
    # a leaf's box holds its triangles, a node's box its subtree's boxes
    low, high = np.asarray(bvh.bounds_min), np.asarray(bvh.bounds_max)
    v0, e1, e2 = (np.asarray(a) for a in (bvh.v0, bvh.e1, bvh.e2))
    for node in np.flatnonzero(leaves):
        rows = slice(first[node], first[node] + count[node])
        points = np.concatenate([v0[rows], v0[rows] + e1[rows], v0[rows] + e2[rows]])
        assert (points >= low[node] - 1e-6).all() and (points <= high[node] + 1e-6).all()
    for node in np.flatnonzero(~leaves):
        inside = slice(node + 1, skip[node])
        assert (low[inside] >= low[node]).all() and (high[inside] <= high[node]).all()
    # the same triangles, in another order
    ours = np.sort(np.round(v0[np.abs(e1).sum(1) > 0] * 1e5).astype(np.int64), axis=0)
    theirs = np.sort(np.round(vertices[faces][:, 0] * 1e5).astype(np.int64), axis=0)
    np.testing.assert_array_equal(ours, theirs)


def test_the_walk_of_the_vectorised_tree_finds_what_brute_force_finds():
    import jax.numpy as jnp

    from tpu_render_cluster.render.mesh import intersect_bvh_packet, intersect_triangles_brute

    bvh, _ = small_tree()
    rng = np.random.default_rng(4)
    origins = rng.normal(size=(256, 3)).astype(np.float32) * 0.1 + np.array([0, 0, -2], np.float32)
    directions = np.array([0, 0, 1], np.float32) + rng.normal(size=(256, 3)).astype(np.float32) * 0.15
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    origins, directions = jnp.asarray(origins), jnp.asarray(directions)
    t_brute, index_brute = intersect_triangles_brute(bvh, origins, directions)
    t_walk, index_walk = intersect_bvh_packet(bvh, origins, directions)
    hit = np.asarray(t_brute) < 1e29
    assert hit.sum() > 100
    np.testing.assert_allclose(np.asarray(t_walk), np.asarray(t_brute), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(index_walk)[hit], np.asarray(index_brute)[hit])


def wide_tables(stream):
    """The staged slab's wide nodes as ``[treelet, wide node, child, 8]``
    and its triangle rows as ``[treelet, leaf slot, 16 triangles, 16]``."""
    from tpu_render_cluster.render import mesh as mesh_module

    slab = np.asarray(stream.tri)
    n_treelets, leaves = slab.shape[0], mesh_module.treelet_leaves(stream)
    nodes = slab[:, 2 * leaves:].reshape(n_treelets, 8, 16, 8).transpose(0, 2, 1, 3)
    rows = slab[:, :2 * leaves].reshape(n_treelets, leaves // 8, 16, 8, 16).transpose(0, 1, 3, 2, 4)
    return nodes, rows.reshape(n_treelets, leaves, 16, 16)


@pytest.mark.parametrize("treelet_leaves", [8, 16, 64])
def test_the_treelets_partition_the_tree(treelet_leaves):
    from tpu_render_cluster.render import mesh as mesh_module

    bvh, _ = small_tree(treelet_leaves)
    stream = bvh.stream
    n_treelets = stream.tri.shape[0]
    assert mesh_module.treelet_leaves(stream) == treelet_leaves
    # one slab a fetch: the triangle rows and one register of wide nodes,
    # which fits the 1,024-word tile the node table had
    assert stream.tri.shape[1:] == (2 * treelet_leaves + 8, 128)
    assert mesh_module.treelet_fetch_bytes(stream) == (treelet_leaves * 16 * 16 + 1024) * 4
    nodes, slots = wide_tables(stream)
    boxes, bits = nodes[..., 0:6], nodes[..., 6]
    child_bit = np.broadcast_to(2.0 ** np.arange(8), bits.shape)
    assert set(np.unique(bits / child_bit)) <= {0.0, 1.0}  # child c's bit is 1 << c, or 0
    present = bits > 0
    # empty slots hold inverted boxes, wherever they are
    assert (boxes[~present][:, 0:3] == 1e30).all() and (boxes[~present][:, 3:6] == -1e30).all()
    assert (boxes[present][:, 0:3] <= boxes[present][:, 3:6]).all()
    # a root and at most max_leaves / 8 groups, children packed from slot 0
    groups = present[:, 0].sum(axis=1)
    assert (groups >= 1).all() and groups.max() <= treelet_leaves // 8
    assert not present[:, 1 + treelet_leaves // 8:].any()
    for held in (present[:, 0], *np.moveaxis(present[:, 1:], 1, 0)):
        assert (held == (np.arange(8) < held.sum(axis=1, keepdims=True))).all()

    # Against the binary tree: its leaves in preorder are the wide nodes'
    # leaves read treelet by treelet, group by group, child by child.
    low, high = np.asarray(bvh.bounds_min), np.asarray(bvh.bounds_max)
    first, count = np.asarray(bvh.first), np.asarray(bvh.count)
    v0 = np.asarray(bvh.v0)
    leaves = iter(np.flatnonzero(count > 0))
    seen = 0
    for t in range(n_treelets):
        assert (present[t, 1:1 + groups[t]].sum(axis=1) >= 1).all()
        assert not present[t, 1 + groups[t]:].any()
        for group in range(groups[t]):
            held = int(present[t, 1 + group].sum())
            members = [next(leaves) for _ in range(held)]
            # a child's box is its leaf's box, a group's the union of its leaves', to the bit
            np.testing.assert_array_equal(boxes[t, 1 + group, :held, 0:3], low[members])
            np.testing.assert_array_equal(boxes[t, 1 + group, :held, 3:6], high[members])
            np.testing.assert_array_equal(boxes[t, 0, group, 0:3], low[members].min(axis=0))
            np.testing.assert_array_equal(boxes[t, 0, group, 3:6], high[members].max(axis=0))
            for child, leaf in enumerate(members):
                # the child's leaf slot is its place: 8 * group + child
                rows = slots[t, 8 * group + child]
                np.testing.assert_array_equal(rows[:count[leaf], 0:3], v0[first[leaf]:first[leaf] + count[leaf]])
                assert not rows[count[leaf]:].any()  # padding rows meet no ray
            assert not slots[t, 8 * group + held:8 * group + 8].any()
            seen += held
    # every leaf of the tree the child of exactly one wide node
    assert next(leaves, None) is None and seen == int((count > 0).sum()) == int(present[:, 1:].sum())
    _, links = top_tables(stream)
    assert sorted(links[links > 0]) == list(range(1, n_treelets + 1))  # every treelet some top node's child, once
    np.testing.assert_array_equal(np.asarray(stream.root)[0], [np.asarray(bvh.bounds_min)[0], np.asarray(bvh.bounds_max)[0]])
    np.testing.assert_array_equal(np.asarray(stream.top_first), [0, len(links)])  # one model: its whole top


def top_tables(stream):
    """The resident top's wide nodes: boxes ``[wide node, child, 8]`` out of
    their tiles of sixteen, links ``[wide node, child]``."""
    links = np.asarray(stream.top_links).astype(np.int64).reshape(-1, 8)
    boxes = np.asarray(stream.top_boxes).reshape(-1, 8, 16, 8).transpose(0, 2, 1, 3).reshape(-1, 8, 8)
    assert boxes.shape[0] == -(-len(links) // 16) * 16
    spare = boxes[len(links):]
    assert (spare[..., 0:3] == 1e30).all() and (spare[..., 3:6] == -1e30).all() and not spare[..., 6:].any()
    return boxes[:len(links)], links


def binary_top(bvh, treelet_leaves):
    """The tree above its treelets by plain recursion over the threaded
    nodes: which nodes are treelet roots (a node of at most
    ``treelet_leaves`` leaves under a parent of more), their numbers in
    preorder, and the depth of the deepest."""
    skip, count = np.asarray(bvh.skip), np.asarray(bvh.count)
    held = np.cumsum(count > 0)
    leaves = lambda node: held[skip[node] - 1] - (held[node - 1] if node else 0)  # noqa: E731
    roots, deepest = {}, 0
    pending = [(0, 0)]
    while pending:
        node, depth = pending.pop()
        if leaves(node) <= treelet_leaves:
            roots[node], deepest = len(roots), max(deepest, depth)
        else:
            pending += [(int(skip[node + 1]), depth + 1), (node + 1, depth + 1)]  # the left one first
    return roots, deepest


@pytest.mark.parametrize("grid, treelet_leaves, wide_nodes", [
    (32, 64, 1), (32, 16, 1), (32, 8, 3),  # 128 leaves: 2, 8 and 16 treelets, one, three and four levels down
    (33, 8, 5),  # 137 leaves: 25 treelets, their roots four AND five levels down: a root of four
    (64, 8, 9),  # 512 leaves: 64 treelets six levels down, two full levels of wide nodes
    (96, 8, 37),  # 1,152 leaves: 256 treelets eight levels down: a root of four and two full levels
])
def test_the_resident_top_is_the_binary_top_three_levels_a_wide_node(grid, treelet_leaves, wide_nodes):
    """Every wide node of the top stands for a binary node and holds what
    lies three levels below it (the model's root: what is left of the
    deepest treelet root's depth after whole threes), a treelet root where
    one is met sooner: in preorder, to the bit, each treelet and each wide
    node but the root the child of exactly one."""
    from tpu_render_cluster.render import mesh as mesh_module

    vertices, faces = mesh_module.make_scan_mesh(grid=grid)
    bvh = mesh_module.build_bvh(vertices, faces, builder="morton", treelet_leaves=treelet_leaves)
    boxes, links = top_tables(bvh.stream)
    roots, deepest = binary_top(bvh, treelet_leaves)
    n_wide, n_treelets = len(links), bvh.stream.tri.shape[0]
    assert (n_wide, n_treelets) == (wide_nodes, len(roots))
    assert sorted(links[links > 0]) == list(range(1, n_treelets + 1))
    assert sorted(-1 - links[links < 0]) == list(range(1, n_wide))  # node 0 is the root, nobody's child
    skip = np.asarray(bvh.skip)
    low, high = np.asarray(bvh.bounds_min), np.asarray(bvh.bounds_max)
    pending, seen = [(0, 0, (deepest - 1) % 3 + 1 if deepest else 3)], 0
    while pending:
        wide, node, levels = pending.pop()
        assert wide == seen  # numbered in the preorder of their binary nodes
        seen += 1
        slots = [node]
        for _ in range(levels):  # a treelet root stands where it is met; what would lie under it is missing
            slots = [
                below for held in slots for below in (
                    (held, None) if held is None or held in roots else (held + 1, int(skip[held + 1]))
                )
            ]
        slots += [None] * (8 - len(slots))
        inner = []
        for child, held in enumerate(slots):
            if held is None:
                assert links[wide, child] == 0 and boxes[wide, child, 6] == 0
                assert (boxes[wide, child, 0:3] == 1e30).all() and (boxes[wide, child, 3:6] == -1e30).all()
                continue
            # the binary node's box to the bit, and the child's own bit
            np.testing.assert_array_equal(boxes[wide, child, 0:3], low[held])
            np.testing.assert_array_equal(boxes[wide, child, 3:6], high[held])
            assert boxes[wide, child, 6] == 1 << child and boxes[wide, child, 7] == 0
            if held in roots:
                assert links[wide, child] == roots[held] + 1
            else:
                inner.append((int(-1 - links[wide, child]), held, 3))
        assert [w for w, _, _ in inner] == sorted(w for w, _, _ in inner) and all(w > wide for w, _, _ in inner)
        pending += reversed(inner)
    assert seen == n_wide
    # lowest bit first, depth first, is the binary walk's order: the treelets as numbered
    order, pending = [], [-1]  # the root's link
    while pending:
        link = pending.pop()
        if link > 0:
            order.append(link - 1)
        else:
            pending += [int(child) for child in links[-1 - link][::-1] if child]
    assert order == list(range(n_treelets))


def one_box_at_a_time(boxes, origins, directions, limit):
    """Which boxes some ray meets, by the kernels' packet test
    (``slab_any``) on one box at a time, in float32."""
    inverse = (1.0 / directions).astype(np.float32)
    met = []
    for box in boxes:
        lo = (box[0:3] - origins) * inverse
        hi = (box[3:6] - origins) * inverse
        tnear = np.minimum(lo, hi).max(axis=1)
        tfar = np.maximum(lo, hi).min(axis=1)
        met.append(bool(((tfar >= np.maximum(tnear, 0.0)) & (tnear < limit)).any()))
    return met


@pytest.mark.parametrize("case", ["all eight", "none", "some", "an empty slot"])
def test_a_wide_test_is_eight_box_tests_and_one_mask(case):
    """A treelet's wide root out of the real tables against a packet of 256
    rays: every child met, none met (through the root's own box), and a
    packet whose limit culls some."""
    import jax.numpy as jnp

    from tpu_render_cluster.render.pallas_kernels import slab_mask

    bvh, _ = small_tree(64)  # 128 leaves: two treelets of eight groups of eight
    nodes, _ = wide_tables(bvh.stream)
    node = nodes[0, 0].copy()
    assert (node[:, 6] == 2.0 ** np.arange(8)).all()
    rng = np.random.default_rng(33)
    low, high = node[:, 0:3].min(axis=0), node[:, 3:6].max(axis=0)
    origins = (rng.uniform(low, high, size=(256, 3)) + [0.0, 0.0, -3.0]).astype(np.float32)
    directions = np.tile(np.float32([1e-3, 2e-3, 1.0]), (256, 1))
    limit = np.full(256, 1e30, np.float32)
    if case == "none":
        # inside the root's box (the top walk would enter), outside every
        # child's: start beyond the far side of each and look away
        origins = origins + np.float32([0.0, 0.0, 3.0 + high[2] - low[2]])
    elif case == "some":
        limit = rng.uniform(2.4, 3.2, 256).astype(np.float32)
        origins, directions = origins[:3].repeat(86, 0)[:256], directions
    elif case == "an empty slot":
        node[5, 0:3], node[5, 3:6], node[5, 6] = 1e30, -1e30, 0.0
    rows = [jnp.asarray(a.reshape(1, 256)) for a in (*origins.T, *(1.0 / directions).astype(np.float32).T)]
    mask = int(slab_mask(jnp.asarray(node), *rows, jnp.asarray(limit.reshape(1, 256))))
    met = one_box_at_a_time(node[:, 0:6], origins, directions, limit)
    if case == "an empty slot":
        assert met[5]  # the test alone takes an inverted box for a hit: its bit is what keeps it out
        met[5] = False
    assert mask == sum(1 << child for child in range(8) if met[child])
    if case in ("all eight", "none"):
        assert mask == {"all eight": 0xFF, "none": 0}[case]
    else:
        assert 0 < mask < 0xFF


def test_resident_or_streamed_follows_the_tables_bytes():
    from tpu_render_cluster.render import mesh as mesh_module

    for kind in ("box", "icosphere"):
        bvh = mesh_module.cached_mesh_bvh(kind)
        assert bvh.stream is None
        assert mesh_module.resident_table_bytes(bvh.v0.shape[0], bvh.skip.shape[0]) < mesh_module.RESIDENT_VMEM_BUDGET
    small, _ = small_tree()
    assert small.stream is None  # 2,048 triangles fit: 4 MiB of padded rows
    assert mesh_module.geometry_bytes(small)["hbm"] == 0 and mesh_module.geometry_bytes(small)["vmem"] > 0
    forced, _ = small_tree(SMALL_TREELET)
    where = mesh_module.geometry_bytes(forced)
    # the slabs in HBM; of the top's three wide nodes the boxes one tile of VMEM, the links eight words of SMEM each
    assert where["hbm"] > 2048 * 64 and where["vmem"] == 8 * 128 * 4 and where["smem"] == 3 * 8 * 4
    # the configuration's mesh, from its shapes: 871,200 rows, 108,899 nodes
    assert mesh_module.resident_table_bytes(871_200, 108_899) > 200 * mesh_module.RESIDENT_VMEM_BUDGET
    assert 2 * mesh_module.SCAN_GRID ** 2 == 871_200


# -- the walk --------------------------------------------------------------------


def bounce_inputs(n=1024, k=6, seed=1):
    import jax.numpy as jnp

    from tpu_render_cluster.render import mesh as mesh_module
    from tpu_render_cluster.render.scene import build_scene

    rng = np.random.default_rng(seed)
    scene = build_scene("03_physics-2-mesh", 295.0)
    scene = scene._replace(radii=jnp.zeros_like(scene.radii))  # no spheres: plane, sky and mesh
    instances = mesh_module.MeshInstances(
        rotation=mesh_module.rotation_y(jnp.asarray(rng.random(k) * 6.28, jnp.float32)),
        translation=jnp.asarray(
            np.stack([rng.random(k) * 6 - 3, 0.6 + rng.random(k), rng.random(k) * 6 - 3], axis=1), jnp.float32
        ),
        albedo=jnp.asarray(0.2 + 0.7 * rng.random((k, 3)), jnp.float32),
        scale=jnp.asarray(0.8 + rng.random(k), jnp.float32),
    )
    origins = np.tile([[8.0, 5.0, 8.0]], (n, 1)) + rng.normal(size=(n, 3)) * 0.01
    target = np.asarray(instances.translation)[rng.integers(0, k, n)] + rng.normal(size=(n, 3)) * 0.4
    directions = target - origins
    directions /= np.linalg.norm(directions, axis=1, keepdims=True)
    return scene, instances, jnp.asarray(origins, jnp.float32), jnp.asarray(directions, jnp.float32)


def brute_force_bounce(bvh, scene, instances, origins, directions):
    """Nearest mesh hit and the shadow ray's fate, by testing every
    triangle of every instance: (t, world normal, albedo, shadowed)."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import mesh as mesh_module

    def nearest(origins, directions):
        best = np.full(origins.shape[0], 1e30, np.float32)
        normal = np.zeros((origins.shape[0], 3), np.float32)
        albedo = np.zeros((origins.shape[0], 3), np.float32)
        for k in range(instances.scale.shape[0]):
            local_o, local_d = mesh_module._rays_to_object_space(instances, k, origins, directions)
            t, index = mesh_module.intersect_triangles_brute(bvh, local_o, local_d)
            t, index = np.asarray(t), np.asarray(index)
            closer = t < best
            world = np.asarray(mesh_module._normals_to_world(instances.rotation[k], bvh.normal[index]))
            best = np.where(closer, t, best)
            normal = np.where(closer[:, None], world, normal)
            albedo = np.where(closer[:, None], np.asarray(instances.albedo[k])[None], albedo)
        return best, normal, albedo

    t, normal, albedo = nearest(origins, directions)
    facing = (normal * np.asarray(directions)).sum(axis=1) < 0
    normal = np.where(facing[:, None], normal, -normal)
    start = np.asarray(origins) + np.asarray(directions) * t[:, None] + normal * 4e-3
    sun = np.broadcast_to(np.asarray(scene.sun_direction), start.shape)
    shadow_t, _, _ = nearest(jnp.asarray(start, jnp.float32), jnp.asarray(sun, jnp.float32))
    return t, normal, albedo, shadow_t < 1e29


@pytest.mark.parametrize("use_tlas", [False, True], ids=["flat", "tlas"])
def test_the_streamed_walk_is_the_resident_walk_bit_for_bit_and_brute_forces_hit(use_tlas, interpreted_kernels):
    import jax.numpy as jnp

    from tpu_render_cluster.render import mesh as mesh_module
    from tpu_render_cluster.render import pallas_kernels

    bvh, _ = small_tree(SMALL_TREELET)
    scene, instances, origins, directions = bounce_inputs()
    n = origins.shape[0]

    def bounce(tree):
        return pallas_kernels.mesh_bounce_pallas(
            scene, mesh_module.MeshSet(tree, instances), origins, directions,
            jnp.ones((n, 3), jnp.float32), jnp.ones((n,), bool), 7, 0,
            total_bounces=4, use_tlas=use_tlas,
        )

    resident = bounce(bvh._replace(stream=None))
    streamed = bounce(bvh)
    assert len(resident) == 6 and len(streamed) == 7
    for ours, theirs in zip(streamed[:5], resident[:5]):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    if use_tlas:
        np.testing.assert_array_equal(np.asarray(streamed[5]), np.asarray(resident[5]))
    visits, fetches, leaf_tests, entries, _, prefetches = (int(x) for x in streamed[6])
    assert visits > leaf_tests > entries >= fetches >= prefetches > 0

    # ... and both are what testing every triangle finds.
    contribution, new_origins, _, throughput, alive = (np.asarray(x) for x in streamed[:5])
    t, normal, albedo, shadowed = brute_force_bounce(bvh, scene, instances, origins, directions)
    o, d = np.asarray(origins), np.asarray(directions)
    t_plane = np.where(d[:, 1] < -1e-8, -o[:, 1] / np.minimum(d[:, 1], -1e-8), 1e30)
    on_mesh = t < t_plane
    assert on_mesh.sum() > 300 and (~on_mesh).sum() > 50
    assert alive[on_mesh].all()
    expected = o + d * t[:, None] + normal * 4e-3
    np.testing.assert_allclose(new_origins[on_mesh], expected[on_mesh], rtol=0, atol=2e-5)  # t and the triangle
    np.testing.assert_allclose(throughput[on_mesh], albedo[on_mesh], rtol=1e-6)  # the instance
    lit = contribution.sum(axis=1) > 0  # no sphere emits and the sky is for misses: the sun term alone
    facing_sun = (normal @ np.asarray(scene.sun_direction)) > 1e-3
    ask = on_mesh & facing_sun
    assert (lit[ask] == ~shadowed[ask]).mean() > 0.995  # the any-hit walk


def top_leaves_met(stream, origin, direction, limit, pad=0.0):
    """The treelets whose box in the resident top (grown by ``pad``) a ray
    meets nearer than ``limit``, in the walk's order over the wide nodes,
    lowest child first and depth first: (treelet, entry t)."""
    boxes, links = top_tables(stream)
    boxes = boxes.astype(np.float64)
    inverse = 1.0 / np.where(np.abs(direction) < 1e-12, 1e-12, direction)
    met, pending = [], [(-1, 0.0)]  # the root's link
    while pending:
        link, near = pending.pop()
        if link > 0:
            met.append((link - 1, near))
            continue
        node = boxes[-1 - link]
        low = (node[:, 0:3] - pad - origin) * inverse
        high = (node[:, 3:6] + pad - origin) * inverse
        near, far = np.minimum(low, high).max(axis=1), np.maximum(low, high).min(axis=1)
        hit = (far >= np.maximum(near, 0.0)) & (near < limit) & (node[:, 6] > 0)
        pending += [(int(links[-1 - link, child]), near[child]) for child in np.flatnonzero(hit)[::-1]]
    return met


def one_walk(treelet_leaves):
    """One instance of the small mesh with its thin axis laid along the
    sun's direction, and 400 seeded object-space rays along it from in
    front of the mesh with brute force's nearest hit: what a launch of ONE
    walk is made of (rays that fly towards the sun hit what faces away from
    it, so no shadow walk follows). ``bounce(start)`` sends a block of 1,024
    copies of the ray from ``start`` through the streamed and the resident
    kernel and returns (streamed, resident, the walk's counts)."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import mesh as mesh_module
    from tpu_render_cluster.render import pallas_kernels
    from tpu_render_cluster.render.scene import build_scene

    bvh, _ = small_tree(treelet_leaves)
    resident = bvh._replace(stream=None)
    scene = build_scene("03_physics-2-mesh", 295.0)
    scene = scene._replace(radii=jnp.zeros_like(scene.radii))
    sun = np.asarray(scene.sun_direction, np.float64)
    # the object's z axis (the mesh's thin one) laid along the sun's direction
    u = np.cross(sun, [0.0, 1.0, 0.0])
    u /= np.linalg.norm(u)
    rotation = np.stack([u, np.cross(sun, u), sun], axis=1)
    translation = np.array([0.0, 3.0, 0.0])
    instances = mesh_module.MeshInstances(
        rotation=jnp.asarray(rotation[None], jnp.float32), translation=jnp.asarray(translation[None], jnp.float32),
        albedo=jnp.full((1, 3), 0.5, jnp.float32), scale=jnp.ones((1,), jnp.float32),
    )
    rng = np.random.default_rng(36)
    low, high = np.asarray(bvh.bounds_min)[0], np.asarray(bvh.bounds_max)[0]
    starts = low + rng.random((400, 3)) * (high - low)
    starts[:, 2] = -2.0
    along = np.array([0.0, 0.0, 1.0])

    def nearest(tree):
        t, _ = mesh_module.intersect_triangles_brute(
            tree, jnp.asarray(starts, jnp.float32), jnp.asarray(np.tile(along, (400, 1)), jnp.float32)
        )
        return np.asarray(t, np.float64)

    def bounce(start, t):
        n = 1024  # one block of the flat sweep
        origins = jnp.asarray(np.tile(rotation @ start + translation, (n, 1)), jnp.float32)
        directions = jnp.asarray(np.tile(sun, (n, 1)), jnp.float32)
        streamed, plain = (
            pallas_kernels.mesh_bounce_pallas(
                scene, mesh_module.MeshSet(tree, instances), origins, directions,
                jnp.ones((n, 3), jnp.float32), jnp.ones((n,), bool), 7, 0, total_bounces=4, use_tlas=False,
            ) for tree in (bvh, resident)
        )
        for ours, theirs in zip(streamed[:5], plain[:5]):
            np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
        hit_point = np.asarray(origins[0], np.float64) + sun * t
        assert abs(np.linalg.norm(np.asarray(streamed[1][0], np.float64) - hit_point) - 4e-3) < 1e-4  # brute force's t
        return dict(zip(pallas_kernels.WALK_COUNTS, (int(x) for x in streamed[6])))

    return bvh, resident, starts, along, nearest, bounce


@pytest.mark.parametrize("case", ["one treelet", "the second culled by the first's hit"])
def test_a_fetch_started_ahead_is_waited_for_where_its_treelet_is_entered(case, interpreted_kernels):
    """One packet of equal rays against one instance, no shadow walk: the
    launch is ONE walk (``one_walk``). A walk that meets one treelet fetches
    it and has nothing to look ahead to. A walk that meets two treelets
    whose second lies wholly behind the first's hit finds the second before
    it has walked the first, with the cull distance of that moment: it
    fetches it, waits for the copy where it enters it, meets no group there,
    and the bounce is the resident walk's bit for bit."""
    bvh, resident, starts, along, nearest, bounce = one_walk(64)  # 128 leaves: two treelets
    assert bvh.stream.tri.shape[0] == 2
    nearest = nearest(resident)

    def fits(start, t):
        met = top_leaves_met(bvh.stream, start, along, 1e30)
        if t > 1e29 or len(top_leaves_met(bvh.stream, start, along, 1e30, pad=1e-3)) != len(met):
            return False
        if case == "one treelet":
            return len(met) == 1
        behind = [treelet for treelet, _ in top_leaves_met(bvh.stream, start, along, t)]
        return len(met) == 2 and behind == [met[0][0]] and met[1][1] > t + 1e-2

    chosen = next(i for i in range(400) if fits(starts[i], nearest[i]))
    counts = bounce(starts[chosen], nearest[chosen])
    expected = 1 if case == "one treelet" else 2
    assert counts["treelet_entries"] == counts["treelet_fetches"] == expected, counts
    assert counts["treelet_prefetches"] == expected - 1, counts
    assert counts["leaf_tests"] > 0 and counts["group_tests"] > 0
    # the treelet found too far ahead costs its fetch and its root's wide test, and nothing else;
    # the top is one wide node here, tested once
    assert counts["node_visits"] == 1 + counts["leaf_tests"] + counts["group_tests"] + counts["treelet_entries"]


@pytest.mark.parametrize("case", ["both behind the first's hit", "the second in front of it"])
def test_a_treelet_left_in_its_parents_mask_is_entered_and_meets_no_group(case, interpreted_kernels):
    """What the wide top changes besides the counts (ISSUE 51): a wide
    node's mask is made when the node is entered, so a later child is met
    under the limit of that moment and not of its own. One packet, ONE walk
    (``one_walk``), eight treelets under the one wide node of the top, and
    a ray whose box meets three of them, the first holding its hit and the
    third wholly behind that hit (the second behind it too, or in front of
    it and missed). A walk that tested the third's box when it came to it
    (the binary top's; the look-ahead's one treelet of grace is spent on
    the second) would cull it and enter two. This one enters all three: the
    third is fetched, waited for, its root's wide test meets no group, and
    the bounce is the resident walk's bit for bit."""
    bvh, resident, starts, along, nearest, bounce = one_walk(16)  # 128 leaves: eight treelets of two groups
    assert bvh.stream.tri.shape[0] == 8 and len(top_tables(bvh.stream)[1]) == 1
    whole = nearest(resident)

    def fits(start, t):
        met = top_leaves_met(bvh.stream, start, along, 1e30)
        if t > 1e29 or len(met) != 3 or len(top_leaves_met(bvh.stream, start, along, 1e30, pad=1e-3)) != 3:
            return False
        in_front = [treelet for treelet, _ in top_leaves_met(bvh.stream, start, along, t)]
        wanted = [met[0][0]] if case == "both behind the first's hit" else [met[0][0], met[1][0]]
        return in_front == wanted and all(entry > t + 1e-2 for treelet, entry in met if treelet not in wanted)

    def first_treelets_hit(i):
        """Ray ``i``'s hit among the triangles of the first treelet it meets alone: a treelet's 16 leaves are 256
        rows of the leaf-ordered triangles."""
        first = 256 * top_leaves_met(bvh.stream, starts[i], along, 1e30)[0][0]
        alone = resident._replace(**{key: getattr(resident, key)[first:first + 256] for key in ("v0", "e1", "e2", "normal")})
        return nearest(alone)[i]

    chosen = next(i for i in range(400) if fits(starts[i], whole[i]) and first_treelets_hit(i) == whole[i])
    counts = bounce(starts[chosen], whole[chosen])
    assert counts["treelet_entries"] == counts["treelet_fetches"] == 3, counts
    assert counts["treelet_prefetches"] == 2, counts  # all but the walk's first are started a treelet ahead
    # one wide test of the top for three entries; a treelet holds two groups, and the third's are never tested
    assert counts["node_visits"] == 1 + 3 + counts["group_tests"] + counts["leaf_tests"], counts
    assert 0 < counts["group_tests"] <= (2 if case == "both behind the first's hit" else 4), counts
    assert counts["leaf_tests"] > 0


# -- the family, and the benchmark's reference ------------------------------------


def scene_arrays(scene_name, frame):
    """What ``benchmark/lib/region_child.py`` hands the reference."""
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    scene = {key: np.asarray(value) for key, value in build_scene(scene_name, frame)._asdict().items()}
    camera = {key: np.asarray(value) for key, value in scene_camera(scene_name, frame)._asdict().items()}
    mesh_set = scene_mesh_set(scene_name, frame)
    mesh = {key: np.asarray(getattr(mesh_set.bvh, key)) for key in ("v0", "e1", "e2")}
    mesh.update({key: np.asarray(value) for key, value in mesh_set.instances._asdict().items()})
    return scene, camera, mesh


@pytest.mark.time_limit(420)
def test_the_scan_familys_frame_agrees_with_the_independent_reference(small_scan_family):
    from benchmark.lib import check
    from benchmark.reference import plain_tracer_accel
    from tpu_render_cluster.render import integrator

    size, samples, frame = 32, 2, 300
    assert small_scan_family.cached_mesh_bvh("scan").stream is not None
    linear = integrator.render_frame_region(
        SCAN_SCENE, frame, y0=0, x0=0, tile_height=size, tile_width=size,
        width=size, height=size, samples=samples, max_bounces=4,
    )
    served = np.asarray(integrator.tonemap(linear))
    assert served.std() > 5.0
    scene, camera, mesh = scene_arrays(SCAN_SCENE, frame)
    assert mesh["v0"].shape[0] == 2 * SMALL_GRID * SMALL_GRID
    replicas = plain_tracer_accel.render_crop_replicas(
        scene, camera, mesh, width=size, height=size, y0=0, x0=0, size=size, samples=samples,
        max_bounces=4, replicas=8, seed=11, min_triangles=mesh["v0"].shape[0],
    )
    ok, excess = check.independent_agreement(served, replicas, block=16, sigmas=5.0, abs_levels=2.5)
    assert ok, f"a block mean lies {excess:.2f} levels beyond the reference's own spread"
    # ... of which 2.5 levels are JPEG's, which this frame never met
    assert check.independent_agreement(served, replicas, block=16, sigmas=5.0, abs_levels=0.0)[0]


def parents_frame(family):
    """Frame 295 at 32x32, 2 spp, 4 bounces of the small ``family`` as the
    program of commit 81f8c34 gives it (before ISSUE 36; sha256 of the bytes
    there ``22a23452610b4c77`` scan, ``c3702055ffca2336`` assets)."""
    from pathlib import Path

    return np.load(Path(__file__).parent / "data" / "streamed_frames_81f8c34.npz")[family]


def assert_the_parents_picture(image, family):
    """The parent's bytes; where another machine's XLA:CPU contracts a
    multiply-add otherwise, a level in a pixel or two, never a surface."""
    differs = np.abs(np.asarray(image).astype(int) - parents_frame(family).astype(int))
    assert differs.max() <= 1 and (differs > 0).mean() <= 0.01, (differs.max(), (differs > 0).sum())


def assert_the_parents_walk(walk, parent):
    """``walk`` [bounces, 6] against the five counts a bounce that the walk
    that fetched a treelet where it entered it, and waited, over a binary
    top gave on the same frame (commit 81f8c34, before ISSUE 36). A treelet
    is now found under an older limit than that walk's: the look-ahead
    culls by the limit as it stood a treelet earlier (ISSUE 36) and a wide
    top node's mask by the limit as it stood when the node was entered
    (ISSUE 51). So a treelet that walk had culled may be fetched and
    entered: its root's wide test meets no group, so NO leaf is tested that
    was not, and no group. Two slots keep one treelet more, so a fetch or
    two may also be spared. The top's steps are wide tests now, eight
    boxes each: far fewer than the binary top's.

    What the stale masks cost here, read per bounce on the two 32 x 32
    frames (PR 51; scan, then assets): entries 744 / 505 / 257 / 138 against
    692 / 474 / 245 / 116 and 552 / 380 / 193 / 131 against 527 / 356 / 182
    / 110, fetches 741 / 499 / 244 / 129 against 692 / 473 / 243 / 115 and
    546 / 375 / 190 / 129 against 527 / 356 / 180 / 110. The first three
    bounces stay inside the limits this helper had (at most 1.075 and
    1.071); the last, a hundred-odd entries of a few scattered rays over
    treelets of 8 leaves, where the eight children of one wide node are
    neighbours a hit in the first would have culled one by one, reads 1.19
    and 1.17. The chip's frames, treelets of 64 leaves, read + 2%."""
    steps, fetches, leaf_tests, entries, group_tests, prefetches = np.asarray(walk).T
    parent_steps, parent_fetches, parent_leaf_tests, parent_entries, parent_group_tests = np.array(parent).T
    np.testing.assert_array_equal(leaf_tests, parent_leaf_tests)
    np.testing.assert_array_equal(group_tests, parent_group_tests)
    more = np.where(np.arange(len(entries)) < len(entries) - 1, 0.1, 0.2)  # as it was, but for the last bounce
    assert (entries >= parent_entries).all() and (entries <= (1 + more) * parent_entries).all(), entries
    assert (np.abs(fetches - parent_fetches) <= more * parent_fetches).all() and fetches.sum() >= parent_fetches.sum(), fetches
    top, parent_top = (s - l - e - g for s, l, e, g in (
        (steps, leaf_tests, entries, group_tests), (parent_steps, parent_leaf_tests, parent_entries, parent_group_tests),
    ))
    assert (top > 0).all() and (top < 0.4 * parent_top).all(), (top, parent_top)
    assert (top <= 1.5 * entries).all(), top / entries  # what the benchmark's walk_top_tests_per_entry reads
    # most fetches are started one treelet ahead; a walk's first cannot be
    assert (prefetches <= fetches).all() and (prefetches > 0.6 * fetches).all(), (prefetches, fetches)


def test_the_scan_familys_frame_program_returns_the_walks_counts(small_scan_family):
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.pallas_kernels import WALK_COUNTS

    image, live, walk = integrator.fused_frame_renderer(SCAN_SCENE, 32, 32, 2, 4, with_live=True)(jnp.float32(295))
    assert image.shape == (32, 32, 3) and image.dtype == jnp.uint8
    live, walk = np.asarray(live), np.asarray(walk)
    assert live.shape == (4, 2) and walk.shape == (4, len(WALK_COUNTS)) == (4, 6)
    steps, fetches, leaf_tests, entries, group_tests, _prefetches = walk.T
    assert (steps >= leaf_tests + entries + group_tests).all()  # the rest are the top's
    assert (entries >= fetches).all() and fetches[0] > 0
    assert (group_tests > 0).all() and (leaf_tests > 0).all()
    # The binary treelet walk (the commit 270e495 on this frame of this
    # family, treelets of 8 leaves; its leaf tests read once with a counter
    # added to a scratch copy) paid these steps for the same picture. A
    # leaf's box is now tested in its group's step, so the steps are fewer;
    # a wide test culls with the best-t it had before its children ran, so
    # the leaves are no fewer.
    binary_steps, binary_leaf_tests = np.array([8240, 5557, 2926, 1382]), np.array([1693, 1094, 463, 204])
    assert (steps < 0.75 * binary_steps).all(), steps
    assert (leaf_tests >= binary_leaf_tests).all() and (leaf_tests < 1.1 * binary_leaf_tests).all(), leaf_tests
    assert_the_parents_walk(
        walk, [[5131, 692, 1737, 692, 692], [3458, 473, 1139, 474, 474], [1861, 243, 483, 245, 245], [918, 115, 212, 116, 116]]
    )
    assert_the_parents_picture(image, "scan")
    # without the counts asked for, the same picture
    plain = integrator.fused_frame_renderer(SCAN_SCENE, 32, 32, 2, 4)(jnp.float32(295))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(image))


def test_off_the_kernels_a_streamed_scene_says_what_it_needs(small_scan_family, monkeypatch):
    from tpu_render_cluster.render.integrator import render_frame

    monkeypatch.setenv("TRC_PALLAS", "0")
    with pytest.raises(NotImplementedError, match="TRC_PALLAS=1"):
        render_frame(SCAN_SCENE, 5, width=16, height=16, samples=1, max_bounces=2)


@pytest.mark.parametrize("crop", [(288, 224), (240, 304), (304, 64)])
def test_the_accelerated_reference_is_the_plain_one_on_the_icosphere_scene(crop):
    from benchmark.reference import plain_tracer, plain_tracer_accel

    scene, camera, mesh = scene_arrays("03_physics-2-mesh", 304)
    shape = dict(
        width=512, height=512, y0=crop[0], x0=crop[1], size=24, samples=2, max_bounces=4, replicas=2, seed=5,
    )
    plain = plain_tracer.render_crop_replicas(scene, camera, mesh, **shape)
    accelerated = plain_tracer_accel.render_crop_replicas(scene, camera, mesh, min_triangles=320, **shape)
    assert plain.std() > 5.0
    # Equal to rounding: display levels of 255. One path in thousands may
    # land on the other side of an edge (another order of the same sums),
    # which moves its pixel and nothing else: the random numbers drawn after
    # it are the same ones.
    apart = (np.abs(accelerated - plain) > 0.05).any(axis=-1)
    assert apart.sum() <= 2, f"{apart.sum()} of {apart.size} pixels differ"
    np.testing.assert_allclose(accelerated, plain, rtol=0, atol=2.0)


@pytest.mark.parametrize("case", ["no mesh", "an icosphere", "a sphere family's arrays"])
def test_the_accelerated_reference_refuses_what_is_not_the_configurations_scene(case):
    from benchmark.reference import plain_tracer_accel

    assert plain_tracer_accel.stated_bodies() == [("03ph2scan-480f-1w", 48, 871_200)]
    scene, camera, mesh = scene_arrays("03_physics-2-mesh", 304)
    handed = {"no mesh": None, "an icosphere": mesh, "a sphere family's arrays": {}}[case]
    with pytest.raises(plain_tracer_accel.Refused, match="871200"):
        plain_tracer_accel.render_crop_replicas(
            scene, camera, handed, width=64, height=64, y0=0, x0=0, size=16, samples=1,
            max_bounces=2, replicas=1, seed=1,
        )


@pytest.mark.parametrize("case", ["whole", "one dropped", "one held twice", "one moved", "padded"])
def test_the_accelerated_reference_takes_only_a_closed_surface(case):
    """The one check of the handed-over triangles that does not come from
    the program: every edge is shared by exactly two triangles."""
    from benchmark.reference import plain_tracer_accel

    scene, camera, mesh = scene_arrays("03_physics-2-mesh", 304)
    v0, e1, e2 = (np.array(mesh[key], np.float32) for key in ("v0", "e1", "e2"))
    if case == "one dropped":
        v0, e1, e2 = v0[1:], e1[1:], e2[1:]
    elif case == "one held twice":
        v0, e1, e2 = (np.concatenate([a, a[7:8]]) for a in (v0, e1, e2))
    elif case == "one moved":
        v0[100] += np.float32(1e-3)
    elif case == "padded":  # rows of zeros are padding, not triangles
        v0, e1, e2 = (np.concatenate([a, np.zeros((5, 3), np.float32)]) for a in (v0, e1, e2))
    handed = {**mesh, "v0": v0, "e1": e1, "e2": e2}
    render = lambda: plain_tracer_accel.render_crop_replicas(  # noqa: E731
        scene, camera, handed, width=64, height=64, y0=24, x0=24, size=8, samples=1,
        max_bounces=1, replicas=1, seed=1, min_triangles=300,
    )
    real = (np.abs(e1).sum(axis=1) > 0) & (np.abs(e2).sum(axis=1) > 0)
    unshared = plain_tracer_accel.unshared_edges(v0[real], e1[real], e2[real])
    if case in ("whole", "padded"):
        assert unshared == 0 and real.sum() == 320
        assert render().shape == (1, 8, 8, 3)
    else:
        assert unshared >= 2
        with pytest.raises(plain_tracer_accel.Refused, match="not shared by exactly two"):
            render()


def test_the_scan_mesh_is_a_closed_surface_by_the_references_own_count():
    from benchmark.reference import plain_tracer_accel
    from tpu_render_cluster.render import mesh as mesh_module

    vertices, faces = mesh_module.make_scan_mesh()
    corners = vertices[faces].astype(np.float32)
    v0, e1, e2 = corners[:, 0], corners[:, 1] - corners[:, 0], corners[:, 2] - corners[:, 0]
    assert len(v0) == 871_200 and plain_tracer_accel.unshared_edges(v0, e1, e2) == 0


# -- the accepted scenes keep their programs --------------------------------------


def pallas_calls(jaxpr):
    import jax

    for equation in jaxpr.eqns:
        if equation.primitive.name == "pallas_call":
            yield equation
        for inner in jax.core.jaxprs_in_params(equation.params):
            yield from pallas_calls(inner)


@pytest.mark.time_limit(420)
def test_the_icosphere_scenes_program_holds_the_kernels_it_held(interpreted_kernels):
    """One launch at full width and one per rung for each later bounce, all
    of the resident kernel: 13 at the worker's shape (PERF.md §5), none of
    them the streamed one, and no new argument that carries anything."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    render = integrator.fused_frame_renderer("03_physics-2-mesh", 512, 512, 8, 4, with_live=True)
    jaxpr = jax.make_jaxpr(render)(jnp.float32(295))
    calls = list(pallas_calls(jaxpr.jaxpr))
    rungs = len(integrator.launch_width_ladder(512 * 512 * 8))
    assert len(calls) == 1 + 3 * rungs == 13
    names = {str(call.params.get("name") or call.params.get("name_and_src_info", "")) for call in calls}
    assert not any("streamed" in name for name in names), names
    assert len(jaxpr.jaxpr.invars) == 1  # the frame, and nothing else


def test_the_scan_scenes_program_streams_every_bounce(small_scan_family):
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    render = integrator.fused_frame_renderer(SCAN_SCENE, 64, 64, 2, 4, with_live=True)
    jaxpr = jax.make_jaxpr(render)(jnp.float32(295))
    calls = list(pallas_calls(jaxpr.jaxpr))
    assert calls and all("mesh_bounce_streamed" in str(call.params) for call in calls)
    # the BLAS is an argument of the jitted program: HBM tables, not constants
    inner = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    # the frame, and BlasStream's arrays
    assert inner and len(inner[-1].invars) == 1 + len(small_scan_family.BlasStream._fields) == 6


# -- the backend's series ---------------------------------------------------------


def test_the_backend_says_where_the_geometry_lives_and_counts_the_walk(small_scan_family, tmp_path, startup_timeline):
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.obs import get_registry
    from tpu_render_cluster.obs.prometheus import render_prometheus
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    def value(text, series):
        lines = [line for line in text.splitlines() if line.startswith(series + " ") or line.startswith(series + "{")]
        return sum(float(line.rsplit(" ", 1)[1]) for line in lines) if lines else None

    before = render_prometheus(get_registry().snapshot())
    backend = TpuRaytraceBackend(base_directory=tmp_path, width=32, height=32, samples=2)
    backend.warm(f"{SCAN_SCENE}_measuring_480f-1w")
    (build,) = [e for e in startup_timeline.events() if e["name"] == "bvh_build"]  # one BLAS: one build
    assert build["args"] == {"model": "scan", "triangles": 2 * SMALL_GRID * SMALL_GRID} and build["dur"] > 0
    job = BlenderJob(
        job_name=f"{SCAN_SCENE}_test", job_description=None, project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py", frame_range_from=295, frame_range_to=296,
        wait_for_number_of_workers=1, frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/frames", output_file_name_format="rendered-######",
        output_file_format="JPEG",
    )
    backend._render_sync(job, 295)
    after = render_prometheus(get_registry().snapshot())
    assert value(after, 'render_geometry_bytes{space="hbm"}') > 2048 * 64
    assert value(after, "render_bvh_build_seconds") > 0 and value(after, "render_geometry_blas_units") == 1
    grown = {}
    for series in (
        "render_walk_node_visits_total", "render_walk_leaf_tests_total", "render_treelet_fetches_total",
        "render_treelet_fetch_bytes_total", "render_treelet_prefetches_total",
    ):
        grown[series] = value(after, series) - (value(before, series) or 0.0)
        assert grown[series] > 0, series
    # leaf tests are some of the steps, and a fetch is a whole slab
    assert grown["render_walk_node_visits_total"] > grown["render_walk_leaf_tests_total"] > grown["render_treelet_fetches_total"]
    # what the benchmark's treelet_prefetch_share reads: most copies are started one treelet ahead
    assert 0.7 * grown["render_treelet_fetches_total"] < grown["render_treelet_prefetches_total"] <= grown["render_treelet_fetches_total"]
    assert grown["render_treelet_fetch_bytes_total"] == grown["render_treelet_fetches_total"] * (8 * 16 * 16 + 1024) * 4
    assert (tmp_path / "frames" / "rendered-000295.jpg").is_file()
