"""The independent reference's triangles, on a mesh small enough to check by hand."""

import numpy as np

from benchmark.reference import plain_tracer as pt

F = np.float32
# a unit octahedron: every face lies in a plane |x| + |y| + |z| = 1
CORNERS = np.array([[1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0], [0, 0, 1], [0, 0, -1]], F)
FACES = [(0, 2, 4), (2, 1, 4), (1, 3, 4), (3, 0, 4), (2, 0, 5), (1, 2, 5), (3, 1, 5), (0, 3, 5)]


def octahedra(translations, scales):
    v0 = np.stack([CORNERS[a] for a, _, _ in FACES])
    return pt.world_triangles({
        "v0": v0, "e1": np.stack([CORNERS[b] for _, b, _ in FACES]) - v0,
        "e2": np.stack([CORNERS[c] for _, _, c in FACES]) - v0,
        "rotation": np.stack([np.eye(3, dtype=F)] * len(scales)),
        "translation": np.asarray(translations, F), "scale": np.asarray(scales, F),
        "albedo": np.linspace(0.2, 0.8, 3 * len(scales), dtype=F).reshape(-1, 3),
    })


def test_the_nearest_triangle_of_the_nearest_instance_is_hit():
    triangles = octahedra([[0, 0, 5], [0, 0, 12]], [1.0, 2.0])
    origins = np.zeros((3, 3), F)
    directions = pt._normalize(np.array([[0, 0, 1], [0.05, 0.05, 1], [1, 0, 0]], F))
    t, normal, albedo = pt._hit_triangles(triangles, origins, directions)
    assert t[0] == np.float32(4.0) and t[2] == pt.INF  # the tip of the near one; a miss
    assert abs(t[1] - 4.0 / (directions[1, 2] - directions[1, 0] - directions[1, 1])) < 1e-4  # its face x + y - z = -4 (about z = 5)
    assert np.allclose(np.abs(normal[1]), 1 / np.sqrt(3), atol=1e-5)
    assert (albedo[1] == triangles["albedo"][0]).all()


def test_a_triangle_on_the_way_to_the_sun_blocks_it():
    triangles = octahedra([[0, 3, 0]], [1.0])
    origins = np.array([[0, 0, 0], [5, 0, 0], [0, 2.5, 0]], F)  # below it, beside it, inside it
    up = np.broadcast_to(np.array([0, 1, 0], F), origins.shape)
    assert pt._any_triangle_towards(triangles, origins, up).tolist() == [True, False, True]


def test_an_image_shows_the_instances():
    scene = {
        "centers": np.zeros((1, 3), F), "radii": np.zeros(1, F), "albedo": np.zeros((1, 3), F),
        "emission": np.zeros((1, 3), F), "plane_albedo_a": np.full(3, 0.8, F), "plane_albedo_b": np.full(3, 0.3, F),
        "sun_direction": pt._normalize(np.array([0.4, 0.8, 0.3], F)), "sun_color": np.full(3, 2.5, F),
        "sky_horizon": np.array([0.65, 0.75, 0.9], F), "sky_zenith": np.array([0.15, 0.3, 0.6], F),
    }
    camera = {
        "origin": np.array([0, 1, -4], F), "forward": np.array([0, 0, 1], F), "right": np.array([1, 0, 0], F),
        "up": np.array([0, 1, 0], F), "tan_half_fov": F(0.4),
    }
    mesh = {
        "v0": CORNERS[[a for a, _, _ in FACES]],
        "e1": CORNERS[[b for _, b, _ in FACES]] - CORNERS[[a for a, _, _ in FACES]],
        "e2": CORNERS[[c for _, _, c in FACES]] - CORNERS[[a for a, _, _ in FACES]],
        "rotation": np.eye(3, dtype=F)[None], "translation": np.array([[0, 1, 0]], F),
        "scale": np.ones(1, F), "albedo": np.array([[0.9, 0.1, 0.1]], F),
    }
    shape = dict(width=16, height=16, y0=0, x0=0, size=16, samples=4, max_bounces=3, replicas=1, seed=5)
    with_mesh = pt.render_crop_replicas(scene, camera, mesh, **shape)[0]
    without = pt.render_crop_replicas(scene, camera, None, **shape)[0]
    centre, corner = (slice(6, 10), slice(6, 10)), (slice(0, 3), slice(0, 3))
    assert with_mesh[centre][..., 0].mean() > with_mesh[centre][..., 2].mean() + 40  # the red octahedron
    assert np.abs(with_mesh[corner] - without[corner]).mean() < 25 < np.abs(with_mesh[centre] - without[centre]).mean()
