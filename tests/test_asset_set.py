"""A scene holds several BLASes: the scene family ``03_physics-2-assets``
(ISSUE 35), whose bodies are instances of several different meshes, all of
them streamed from HBM out of one table.

Small and seeded: three scan meshes (grids 16 / 24 / 32: 512, 1,152 and
2,048 triangles, of three proportions), streaming forced by a small treelet
budget, the Pallas interpreter on the CPU. What is held:

- the set's tables are the models' tables end to end with the wide tops'
  links and treelet numbers moved along, and the tables of a set of ONE model are that
  model's own, bit for bit, as is the bounce over them;
- a walk of the set never leaves its instance's nodes of the top: with
  every instance one model, the set's counts and hits are that model's
  alone; a set of three equal models renders the one-model scene's bytes;
- the set's walk gives every instance the hit that testing every triangle
  of its own model gives (distance and triangle through the new origin, the
  instance through the throughput, the any-hit through the sun term);
- the family's frame agrees with the benchmark's independent reference
  (``plain_tracer_assets``) by the check's own rule, a frame whose bodies
  are all model 0 does not, and that reference is ``plain_tracer_accel``
  where there is one model and refuses what is not the stated scene;
- the build refuses a top past its share of VMEM or SMEM or deeper than the
  walk's stack, and a set that mixes resident and streamed;
- the accepted families keep their programs, and the backend says how many
  BLASes there are and splits the walk's steps.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import test_scan_stream as scan_tests
from test_scan_stream import interpreted_kernels, pallas_calls, scene_arrays  # noqa: F401

ASSETS_SCENE = "03_physics-2-assets"
SMALL_TREELET = 8
# name: (grid, seed, tube over major); three shapes, not one shape thrice
SMALL_MODELS = {"fat": (16, 3, 1.1), "ring": (24, 5, None), "thin": (32, 7, 0.3)}
SMALL_TRIANGLES = [2 * grid * grid for grid, _, _ in SMALL_MODELS.values()]


def small_makers(names=tuple(SMALL_MODELS)):
    from tpu_render_cluster.render import mesh as mesh_module

    return {name: functools.partial(mesh_module.make_scan_mesh, *SMALL_MODELS[name]) for name in names}


def small_set(names=tuple(SMALL_MODELS)):
    from tpu_render_cluster.render import mesh as mesh_module

    return mesh_module.morton_bvh_set(small_makers(names), treelet_leaves=SMALL_TREELET)


def small_single(name):
    from tpu_render_cluster.render import mesh as mesh_module

    return mesh_module.build_bvh(*small_makers()[name](), builder="morton", treelet_leaves=SMALL_TREELET)


@pytest.fixture
def small_assets_family(monkeypatch, interpreted_kernels):  # noqa: F811
    """The assets family over the three small models, streamed: the models,
    the VMEM budget and the treelet size are the module's constants, so a
    test changes them there and nowhere in the program."""
    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render import mesh as mesh_module

    monkeypatch.setattr(mesh_module, "ASSET_MODELS", {
        name: mesh_module.ScanModel(name, 2 * grid * grid, grid, seed, ratio)
        for name, (grid, seed, ratio) in SMALL_MODELS.items()
    })
    monkeypatch.setattr(mesh_module, "RESIDENT_VMEM_BUDGET", 0)
    monkeypatch.setattr(mesh_module, "TREELET_LEAVES", SMALL_TREELET)

    def forget():
        mesh_module.reset_geometry_cache()
        integrator.fused_frame_renderer.cache_clear()
        integrator.fused_region_renderer.cache_clear()

    forget()
    yield mesh_module
    forget()


def whole_bounce(tree, instances, use_tlas=True, k=6):
    """Bounce 0 of 4 over ``tree`` for ``bounce_inputs(k=k)``'s scene and
    rays, with the instances as given."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import mesh as mesh_module
    from tpu_render_cluster.render import pallas_kernels

    scene, _, origins, directions = scan_tests.bounce_inputs(k=k)
    n = origins.shape[0]
    return pallas_kernels.mesh_bounce_pallas(
        scene, mesh_module.MeshSet(tree, instances), origins, directions,
        jnp.ones((n, 3), jnp.float32), jnp.ones((n,), bool), 7, 0, total_bounces=4, use_tlas=use_tlas,
    )


# -- the tables --------------------------------------------------------------------


def test_the_sets_tables_are_the_models_tables_end_to_end():
    bvh = small_set()
    stream = bvh.stream
    singles = [small_single(name) for name in SMALL_MODELS]
    slabs = np.cumsum([0] + [s.stream.tri.shape[0] for s in singles])
    nodes = np.cumsum([0] + [s.stream.top_links.shape[0] // 8 for s in singles])
    rows = np.cumsum([0] + [s.v0.shape[0] for s in singles])
    np.testing.assert_array_equal(np.asarray(stream.top_first), nodes)
    np.testing.assert_array_equal(bvh.tri_first, rows)
    assert stream.root.shape == (3, 2, 3) and bvh.skip is None
    boxes, links = scan_tests.top_tables(stream)
    assert len(links) == nodes[-1] and np.diff(nodes).tolist() == [1, 3, 3]  # a top that is its root alone, and two of two levels
    for m, single in enumerate(singles):
        own = single.stream
        np.testing.assert_array_equal(np.asarray(stream.tri[slabs[m]:slabs[m + 1]]), np.asarray(own.tri))
        np.testing.assert_array_equal(np.asarray(stream.root[m]), np.asarray(own.root[0]))
        # the model's wide nodes, boxes to the bit, sixteen a tile wherever the model begins
        np.testing.assert_array_equal(boxes[nodes[m]:nodes[m + 1]], scan_tests.top_tables(own)[0])
        mine, own_links = links[nodes[m]:nodes[m + 1]], scan_tests.top_tables(own)[1]
        # links count from the tables' start: a wide node's past the earlier models' nodes, a treelet's past their slabs
        wide, treelet = own_links < 0, own_links > 0
        np.testing.assert_array_equal(mine[wide], own_links[wide] - nodes[m])
        np.testing.assert_array_equal(mine[treelet], own_links[treelet] + slabs[m])
        assert not mine[~wide & ~treelet].any()
        # a walk of model m begins at its first node and follows links, which stay among the model's own
        assert (nodes[m] < -1 - mine[wide]).all() and (-1 - mine[wide] < nodes[m + 1]).all()
        assert (slabs[m] < mine[treelet]).all() and (mine[treelet] <= slabs[m + 1]).all()
        for key in ("v0", "e1", "e2", "normal"):
            np.testing.assert_array_equal(getattr(bvh, key)[rows[m]:rows[m + 1]], np.asarray(getattr(single, key)))
        np.testing.assert_array_equal(bvh.bounds_min[m], np.asarray(single.bounds_min)[0])


@pytest.mark.parametrize("use_tlas", [False, True], ids=["flat", "tlas"])
def test_a_set_of_one_model_is_todays_single_blas_bit_for_bit(use_tlas, interpreted_kernels):  # noqa: F811
    one, single = small_set(("thin",)), small_single("thin")
    assert one.stream._fields == single.stream._fields
    for ours, theirs in zip(one.stream, single.stream):
        assert ours.dtype == theirs.dtype
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    _, instances, _, _ = scan_tests.bounce_inputs()
    alone = whole_bounce(single, instances, use_tlas)  # no instance names a model: the one there is
    as_set = whole_bounce(one, instances._replace(model=np.zeros(instances.scale.shape[0], np.int32)), use_tlas)
    for ours, theirs in zip(as_set, alone):
        if ours is not None:
            np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


# -- the walk ------------------------------------------------------------------------


@pytest.mark.parametrize("m, name", list(enumerate(SMALL_MODELS)))
def test_a_walk_never_leaves_its_instances_nodes_of_the_top(m, name, interpreted_kernels):  # noqa: F811
    """Every instance model ``m`` of the set: the walk begins at that
    model's root, its first wide node of the top, and follows links that
    stay among the model's own nodes and slabs, so its six counts and
    every hit are those of the model's tree alone. A walk that read
    another model's nodes would count other steps."""
    _, instances, _, _ = scan_tests.bounce_inputs()
    k = instances.scale.shape[0]
    in_set = whole_bounce(small_set(), instances._replace(model=np.full(k, m, np.int32)))
    alone = whole_bounce(small_single(name), instances)
    assert int(in_set[6][0]) > 0
    for ours, theirs in zip(in_set, alone):
        if ours is not None:
            np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_three_equal_models_render_the_one_model_scenes_bytes(small_assets_family):
    """The family over three copies of one mesh against the family over
    that mesh alone (body i mod 1): the same image, and the same steps."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    mesh_module = small_assets_family
    one = mesh_module.ScanModel("ring", 1152, 24, 5, None)

    def frame(models):
        mesh_module.ASSET_MODELS = models  # the fixture puts the module's own back
        mesh_module.reset_geometry_cache()
        integrator.fused_frame_renderer.cache_clear()
        image, _live, walk = integrator.fused_frame_renderer(ASSETS_SCENE, 32, 32, 1, 4, with_live=True)(jnp.float32(295))
        return np.asarray(image), np.asarray(walk)

    image, walk = frame({"a": one, "b": one, "c": one})
    assert mesh_module.blas_count(mesh_module.cached_mesh_bvh("assets")) == 3
    alone_image, alone_walk = frame({"a": one})
    assert mesh_module.blas_count(mesh_module.cached_mesh_bvh("assets")) == 1
    assert image.std() > 5.0 and walk[:, 0].sum() > 0
    np.testing.assert_array_equal(image, alone_image)
    np.testing.assert_array_equal(walk[:, [0, 2, 3, 4]], alone_walk[:, [0, 2, 3, 4]])  # fetches differ: three copies of a slab


def test_the_assets_familys_frame_is_the_parents_and_no_leaf_is_tested_that_was_not(small_assets_family):
    """Frame 295 of the family over the three small models against what the
    walk that fetched a treelet where it entered it gave (commit 81f8c34,
    before ISSUE 36; `test_scan_stream` says the same of one BLAS): the
    look-ahead of every model's walk ends where its own top ends, so the
    picture, the leaves and the groups tested are that walk's, and only
    treelets found too far ahead are entered besides."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    image, _live, walk = integrator.fused_frame_renderer(ASSETS_SCENE, 32, 32, 2, 4, with_live=True)(jnp.float32(295))
    scan_tests.assert_the_parents_walk(
        walk, [[3780, 527, 1300, 527, 527], [2645, 356, 878, 356, 356], [1404, 180, 386, 182, 182], [893, 110, 245, 110, 110]]
    )
    scan_tests.assert_the_parents_picture(image, "assets")


def brute_force_bounce(singles, scene, instances, origins, directions):
    """`test_scan_stream.brute_force_bounce` with each instance tested
    against every triangle of ITS model."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import mesh as mesh_module

    model = np.asarray(instances.model)

    def nearest(origins, directions):
        best = np.full(origins.shape[0], 1e30, np.float32)
        normal = np.zeros((origins.shape[0], 3), np.float32)
        albedo = np.zeros((origins.shape[0], 3), np.float32)
        for k in range(instances.scale.shape[0]):
            bvh = singles[model[k]]
            local_o, local_d = mesh_module._rays_to_object_space(instances, k, origins, directions)
            t, index = (np.asarray(x) for x in mesh_module.intersect_triangles_brute(bvh, local_o, local_d))
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
def test_the_sets_walk_finds_what_brute_force_over_each_instances_own_model_finds(use_tlas, interpreted_kernels):  # noqa: F811
    bvh = small_set()
    singles = [small_single(name)._replace(stream=None) for name in SMALL_MODELS]
    scene, instances, origins, directions = scan_tests.bounce_inputs(k=9)
    instances = instances._replace(model=(np.arange(9) % 3).astype(np.int32))
    streamed = whole_bounce(bvh, instances, use_tlas, k=9)
    visits, fetches, leaf_tests, entries, group_tests, prefetches = (int(x) for x in streamed[6])
    assert visits > leaf_tests + entries + group_tests and entries >= fetches >= prefetches > 0

    contribution, new_origins, _, throughput, alive = (np.asarray(x) for x in streamed[:5])
    t, normal, albedo, shadowed = brute_force_bounce(singles, scene, instances, origins, directions)
    o, d = np.asarray(origins), np.asarray(directions)
    t_plane = np.where(d[:, 1] < -1e-8, -o[:, 1] / np.minimum(d[:, 1], -1e-8), 1e30)
    on_mesh = t < t_plane
    assert on_mesh.sum() > 300 and (~on_mesh).sum() > 50
    # every model is hit by many rays: the albedo names the instance, the instance its model
    hit_model = np.array([
        np.asarray(instances.model)[np.abs(np.asarray(instances.albedo) - a).sum(axis=1).argmin()] for a in albedo[on_mesh]
    ])
    assert (np.bincount(hit_model, minlength=3) > 40).all()
    assert alive[on_mesh].all()
    expected = o + d * t[:, None] + normal * 4e-3
    np.testing.assert_allclose(new_origins[on_mesh], expected[on_mesh], rtol=0, atol=2e-5)  # t and the triangle
    np.testing.assert_allclose(throughput[on_mesh], albedo[on_mesh], rtol=1e-6)  # the instance
    lit = contribution.sum(axis=1) > 0
    ask = on_mesh & ((normal @ np.asarray(scene.sun_direction)) > 1e-3)
    assert (lit[ask] == ~shadowed[ask]).mean() > 0.995  # the any-hit walk

    # ... and a walk that gave every instance model 0's tree finds something else
    wrong = whole_bounce(bvh, instances._replace(model=np.zeros(9, np.int32)), use_tlas, k=9)
    assert np.abs(np.asarray(wrong[1]) - new_origins).max() > 1e-2


# -- the family, and the benchmark's reference -----------------------------------------


# the configuration's width, and a crop on three bodies of three models
CROP = dict(width=512, height=512, y0=240, x0=224)


def family_crop(size, samples, frame):
    from tpu_render_cluster.render import integrator

    linear = integrator.render_frame_region(
        ASSETS_SCENE, frame, tile_height=size, tile_width=size, samples=samples, max_bounces=4, **CROP,
    )
    return np.asarray(integrator.tonemap(linear))


@pytest.mark.time_limit(600)
def test_the_assets_familys_frame_agrees_with_the_independent_reference_and_one_model_for_all_does_not(
    small_assets_family, monkeypatch,
):
    from benchmark.lib import check
    from benchmark.reference import plain_tracer_assets
    from tpu_render_cluster.render import integrator, scene as scene_module

    size, samples, frame = 64, 4, 300
    served = family_crop(size, samples, frame)
    assert served.std() > 5.0
    scene, camera, mesh = scene_arrays(ASSETS_SCENE, frame)
    assert mesh["v0"].shape[0] == sum(SMALL_TRIANGLES)
    np.testing.assert_array_equal(mesh["model"], np.arange(48) % 3)
    np.testing.assert_array_equal(mesh["tri_count"], np.array(SMALL_TRIANGLES)[np.arange(48) % 3])
    replicas = plain_tracer_assets.render_crop_replicas(
        scene, camera, mesh, size=size, samples=samples, max_bounces=4, replicas=8, seed=11,
        stated=(48, SMALL_TRIANGLES), **CROP,
    )
    rule = dict(block=16, sigmas=5.0, abs_levels=2.5)
    ok, excess = check.independent_agreement(served, replicas, **rule)
    assert ok, f"a block mean lies {excess:.2f} levels beyond the reference's own spread"

    # the control: the same program with every body given model 0's BLAS
    build = scene_module.build_mesh_instances

    def all_model_zero(name, frame):
        instances = build(name, frame)
        return instances._replace(model=np.zeros_like(instances.model))

    monkeypatch.setattr(scene_module, "build_mesh_instances", all_model_zero)
    integrator.fused_region_renderer.cache_clear()
    control = family_crop(size, samples, frame)
    ok, excess = check.independent_agreement(control, replicas, **rule)
    assert not ok and excess > 1.0, excess  # sound -3.2 levels, the control +2.3


def test_the_assets_reference_with_one_model_is_the_accelerated_reference():
    from benchmark.reference import plain_tracer_accel, plain_tracer_assets

    scene, camera, mesh = scene_arrays("03_physics-2-mesh", 304)
    assert (mesh["model"] == 0).all() and (mesh["tri_first"] == 0).all() and (mesh["tri_count"] == len(mesh["v0"])).all()
    shape = dict(width=512, height=512, y0=288, x0=224, size=24, samples=2, max_bounces=4, replicas=2, seed=5)
    one_tree = plain_tracer_accel.render_crop_replicas(scene, camera, mesh, min_triangles=320, **shape)
    a_set_of_one = plain_tracer_assets.render_crop_replicas(scene, camera, mesh, stated=(48, [320]), **shape)
    assert one_tree.std() > 5.0
    np.testing.assert_array_equal(a_set_of_one, one_tree)


@pytest.mark.parametrize("case", [
    "no mesh", "one model for all", "models swapped", "a model missing", "another count", "not 48 bodies",
    "no model fields", "an open surface",
])
def test_the_assets_reference_refuses_what_is_not_the_stated_scene(case, small_assets_family):
    from benchmark.reference import plain_tracer_assets
    from benchmark.reference.plain_tracer_accel import Refused

    scene, camera, mesh = scene_arrays(ASSETS_SCENE, 304)
    first = np.concatenate([[0], np.cumsum(SMALL_TRIANGLES)])
    stated, match = (48, SMALL_TRIANGLES), "not a configuration's scene"
    if case == "no mesh":
        mesh, match = None, "handed over no mesh"
    elif case == "one model for all":  # what the control's program hands over
        mesh = {**mesh, "model": np.zeros(48, np.int32), "tri_first": np.zeros(48, np.int32),
                "tri_count": np.full(48, SMALL_TRIANGLES[0], np.int32)}
        match = "models hold 512 rows of the 3712"
    elif case == "models swapped":  # the same triangles, models 1 and 2 the other way round
        order = np.concatenate([np.arange(first[0], first[1]), np.arange(first[2], first[3]), np.arange(first[1], first[2])])
        counts = np.array([SMALL_TRIANGLES[0], SMALL_TRIANGLES[2], SMALL_TRIANGLES[1]])
        starts = np.concatenate([[0], np.cumsum(counts)])
        mesh = {**mesh, **{key: mesh[key][order] for key in ("v0", "e1", "e2")},
                "tri_first": starts[mesh["model"]].astype(np.int32), "tri_count": counts[mesh["model"]].astype(np.int32)}
    elif case == "a model missing":
        keep = mesh["model"] < 2
        mesh = {key: (value[:first[2]] if key in ("v0", "e1", "e2") else value[keep]) for key, value in mesh.items()}
    elif case == "another count":
        stated = (48, [SMALL_TRIANGLES[0], SMALL_TRIANGLES[1], SMALL_TRIANGLES[2] + 2])
    elif case == "not 48 bodies":
        stated = (45, SMALL_TRIANGLES)
    elif case == "no model fields":
        mesh = {key: value for key, value in mesh.items() if key not in ("model", "tri_first", "tri_count")}
        match = "carry no 'model'"
    elif case == "an open surface":
        mesh = {**mesh, "v0": mesh["v0"].copy()}
        mesh["v0"][first[1] + 100] += np.float32(1e-3)
        match = "edges of model 1 are not shared by exactly two"
    with pytest.raises(Refused, match=match):
        plain_tracer_assets.render_crop_replicas(
            scene, camera, mesh, width=64, height=64, y0=24, x0=24, size=8, samples=1,
            max_bounces=1, replicas=1, seed=1, stated=stated,
        )


def test_the_assets_reference_reads_the_models_its_configuration_states():
    from benchmark.reference import plain_tracer_assets
    from tpu_render_cluster.render import mesh as mesh_module

    ((name, bodies, counts),) = plain_tracer_assets.stated_scenes()
    assert name == "03ph2assets-480f-1w" and bodies == 48
    assert counts == [2 * model.grid ** 2 for model in mesh_module.ASSET_MODELS.values()]
    assert mesh_module.ASSET_MODELS["dragon"][2:] == (mesh_module.SCAN_GRID, mesh_module.SCAN_SEED, None)


# -- what the build refuses ------------------------------------------------------------


def fake_tables(slabs, nodes):
    """Tables of the right shapes with nothing in them: ``nodes`` wide nodes
    in a chain, the last one's first child treelet 0."""
    links = np.zeros((nodes, 8), np.int32)
    links[:-1, 0] = -1 - np.arange(1, nodes)
    links[-1, 0] = 1
    return dict(
        tri=np.zeros((slabs, 1, 1), np.float32), top_boxes=np.zeros((nodes, 8, 8), np.float32),
        top_links=links.reshape(-1), root=np.zeros((1, 2, 3), np.float32), top_first=np.array([0, nodes], np.int32),
    )


@pytest.mark.parametrize("case,match", [
    ("a set's boxes past VMEM's share", "its share of VMEM"), ("one BLAS's boxes past VMEM's share", "its share of VMEM"),
    ("a top deeper than the walk's stack", "outgrows the walk's stack"), ("two treelet sizes", "one treelet size"),
])
def test_the_build_refuses_what_the_top_cannot_hold(case, match, monkeypatch):
    """The limits as they stand since the top is wide (ISSUE 51): 256 B of
    VMEM a wide node against the boxes' share of it, refused where every
    build's tables become the kernel's operands (``blas_stream``: one BLAS
    and a set alike), and one stack level a level of wide nodes. The 16-bit
    links went with the binary top's packed words: a link is a whole word
    now, and at the budget the links are 512 KiB of SMEM."""
    from tpu_render_cluster.render import mesh as mesh_module

    assert (mesh_module.TOP_NODE_BYTES, mesh_module.TOP_VMEM_BUDGET) == (8 * 8 * 4, 4 << 20)
    if case == "a top deeper than the walk's stack":
        # 128 leaves in treelets of 8: roots four levels down, a root of two and a level of two wide nodes
        assert scan_tests.small_tree(SMALL_TREELET)[0].stream.top_links.shape[0] == 3 * 8
        monkeypatch.setattr(mesh_module, "TOP_LEVELS", 1)
        with pytest.raises(ValueError, match=match):
            scan_tests.small_tree(SMALL_TREELET)
        monkeypatch.setattr(mesh_module, "TOP_LEVELS", 2)
        assert scan_tests.small_tree(SMALL_TREELET)[0].stream.top_links.shape[0] == 3 * 8
        return
    if case == "two treelet sizes":
        with pytest.raises(ValueError, match=match):
            mesh_module.join_treelet_tables([fake_tables(10, 10), {**fake_tables(10, 10), "tri": np.zeros((10, 2, 1), np.float32)}])
        return
    # 16,385 wide nodes are 4 MiB + 256 B; one fewer fits, far past the 32,767 treelets and 65,535 nodes of the 16-bit links
    over, fits = {
        "a set's boxes past VMEM's share": (
            lambda: mesh_module.join_treelet_tables([fake_tables(10, 10_000), fake_tables(10, 6_385)]),
            lambda: mesh_module.join_treelet_tables([fake_tables(40_000, 10_000), fake_tables(10, 6_384)]),
        ),
        "one BLAS's boxes past VMEM's share": (lambda: fake_tables(10, 16_385), lambda: fake_tables(40_010, 16_384)),
    }[case]
    with pytest.raises(ValueError, match=match):
        mesh_module.blas_stream(over())
    stream = mesh_module.blas_stream(fits())
    assert stream.top_boxes.shape == (1024 * 8, 128) and stream.top_links.shape == (16_384 * 8,)
    assert mesh_module.geometry_bytes(mesh_module.traced_stream_bvh(stream)) == {
        "hbm": 40_010 * 4, "vmem": 4 << 20, "smem": 512 << 10,
    }
    if case.startswith("a set"):
        assert stream.top_first.tolist() == [0, 10_000, 16_384]
        links = np.asarray(stream.top_links).reshape(-1, 8)
        assert links[10_000, 0] == -1 - 10_001 and links[-1, 0] == 1 + 40_000  # treelet 0 of the second model is slab 40,000


def test_a_set_that_mixes_resident_and_streamed_is_refused():
    from tpu_render_cluster.render import mesh as mesh_module

    with pytest.raises(ValueError, match="one resident BLAS or all streamed, not a mix"):
        mesh_module.morton_bvh_set(small_makers())  # 512 triangles fit VMEM: no treelet size forces streaming


# -- the programs ------------------------------------------------------------------------


def test_the_assets_scenes_program_streams_every_bounce_from_one_table(small_assets_family):
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    render = integrator.fused_frame_renderer(ASSETS_SCENE, 64, 64, 2, 4, with_live=True)
    jaxpr = jax.make_jaxpr(render)(jnp.float32(295))
    calls = list(pallas_calls(jaxpr.jaxpr))
    assert calls and all("mesh_bounce_streamed" in str(call.params) for call in calls)
    # as many launches as the scan family's program of this shape: one walk, not one a model
    scan = integrator.fused_frame_renderer("03_physics-2-scan", 64, 64, 2, 4, with_live=True)
    assert len(calls) == len(list(pallas_calls(jax.make_jaxpr(scan)(jnp.float32(295)).jaxpr)))
    inner = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    assert inner and len(inner[-1].invars) == 1 + len(small_assets_family.BlasStream._fields)


def test_the_job_name_finds_the_family_and_older_names_find_theirs():
    from tpu_render_cluster.render.scene import SCENE_NAMES, mesh_kind_for_scene, scene_for_job_name

    assert ASSETS_SCENE in SCENE_NAMES
    assert scene_for_job_name("03_physics-2-assets_measuring_480f-1w") == ASSETS_SCENE
    assert scene_for_job_name("03_physics-2-scan_measuring_480f-1w") == "03_physics-2-scan"
    assert scene_for_job_name("03_physics-2-mesh_x") == "03_physics-2-mesh"
    assert scene_for_job_name("03ph2_grid") == "03_physics-2"  # never a mesh family by its number alone
    assert mesh_kind_for_scene(ASSETS_SCENE) == "assets" and mesh_kind_for_scene("03_physics-2") is None


# -- the backend's series ------------------------------------------------------------------


def test_the_backend_counts_the_blases_times_each_build_and_splits_the_steps(small_assets_family, tmp_path, startup_timeline):
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.obs import get_registry
    from tpu_render_cluster.obs.prometheus import render_prometheus
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    def value(text, series):
        lines = [line for line in text.splitlines() if line.startswith(series + " ") or line.startswith(series + "{")]
        return sum(float(line.rsplit(" ", 1)[1]) for line in lines) if lines else None

    before = render_prometheus(get_registry().snapshot())
    backend = TpuRaytraceBackend(base_directory=tmp_path, width=32, height=32, samples=2)
    backend.warm(f"{ASSETS_SCENE}_measuring_480f-1w")
    # one span a model on the worker's timeline, through the start-up recorder
    builds = [e for e in startup_timeline.events() if e["name"] == "bvh_build"]
    assert [(e["cat"], e["args"]["model"], e["args"]["triangles"]) for e in builds] == [
        ("render", "fat", 512), ("render", "ring", 1152), ("render", "thin", 2048), ("render", "upload", 0),
    ]
    job = BlenderJob(
        job_name=f"{ASSETS_SCENE}_test", job_description=None, project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py", frame_range_from=295, frame_range_to=296,
        wait_for_number_of_workers=1, frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/frames", output_file_name_format="rendered-######",
        output_file_format="JPEG",
    )
    backend._render_sync(job, 295)
    after = render_prometheus(get_registry().snapshot())
    assert value(after, "render_geometry_blas_units") == 3
    held = small_assets_family.geometry_bytes(small_assets_family.cached_mesh_bvh("assets"))
    assert value(after, 'render_geometry_bytes{space="hbm"}') == held["hbm"] > sum(SMALL_TRIANGLES) * 64
    assert value(after, 'render_geometry_bytes{space="smem"}') == held["smem"]
    for build in builds:  # a gauge a model: what the benchmark's bvh_build_s sums
        model, seconds = build["args"]["model"], build["dur"] / 1e6
        assert seconds > 0 and value(after, f'render_bvh_build_seconds{{model="{model}"}}') == pytest.approx(seconds, abs=1e-6)
    grown = {}
    for series in (
        "render_walk_node_visits_total", "render_walk_leaf_tests_total",
        "render_walk_treelet_entries_total", "render_walk_group_tests_total",
    ):
        grown[series] = value(after, series) - (value(before, series) or 0.0)
        assert grown[series] > 0, series
    top = grown.pop("render_walk_node_visits_total") - sum(grown.values())
    assert top > 0  # the steps of the resident top: what the benchmark's walk_top_step_share reads
    assert (tmp_path / "frames" / "rendered-000295.jpg").is_file()
