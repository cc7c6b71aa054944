"""The deep per-bounce path picks each bounce's width from its live count.

The ladder and the rung (pure functions), and the program: whatever widths
it runs its bounces at, the linear image is the full-width program's. The
full-width reference is the same code with the ladder function patched to
the one rung ``n``; every program here is traced inside a ``jax.jit`` of
this file's own (through ``render_tile.__wrapped__`` and an uncached region
renderer), keyed by the ladder in force, so the patch is what the trace reads. Pallas interpreter on the
CPU, tiny frames.

On the chip the two programs' images are equal bit for bit (PERF.md §6,
PR 29: 0 of 262,144 linear pixels differ on four frames): a width changes
the kernel's grid, not its code. Under the interpreter the kernel is XLA:CPU
code, whose multiply-adds are contracted or not by what surrounds them, so
two differently shaped programs may give one ray in thousands another last
bit in the SAME full-width bounce (seen on frame 30 at 32x32x2: one pixel,
2e-6). ``assert_the_same_image`` allows two such pixels and nothing more.
"""

from __future__ import annotations

import numpy as np
import pytest

DEEP_SCENE = "03_physics-2-mesh"
BOUNCES = 4


@pytest.fixture
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("TRC_PALLAS", "1")


def one_rung(n: int) -> tuple[int, ...]:
    return (n,)


def assert_the_same_image(image, reference):
    assert image.shape == reference.shape and image.dtype == reference.dtype == np.float32
    differing = (image != reference).any(axis=-1)
    assert differing.sum() <= 2, f"{differing.sum()} pixels differ"
    np.testing.assert_allclose(image, reference, rtol=1e-4, atol=1e-5)


# -- the ladder ------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 700, 1024, 2048, 8192, 30000, 512 * 512 * 8])
def test_the_ladder_is_descending_multiples_of_the_block_under_n(n):
    from tpu_render_cluster.render.integrator import launch_width_ladder
    from tpu_render_cluster.render.pallas_kernels import BVH_BLOCK_R, tlas_block_r

    widths = launch_width_ladder(n)
    assert widths[0] == n and len(widths) <= 4
    assert list(widths) == sorted(set(widths), reverse=True)
    assert BVH_BLOCK_R % tlas_block_r() == 0  # a rung is whole blocks of either kernel variant
    for width in widths[1:]:
        assert width % BVH_BLOCK_R == 0 and n // 16 <= width <= -(-n // 4 // BVH_BLOCK_R) * BVH_BLOCK_R
    if n <= BVH_BLOCK_R:
        assert widths == (n,)
    if n == 512 * 512 * 8:
        assert widths == (n, n // 4, n // 8, n // 16)


@pytest.mark.parametrize("n", [2048, 8192, 30000])
def test_the_rung_holds_the_live_rays_and_never_widens(n):
    from tpu_render_cluster.render.integrator import launch_rung, launch_width_ladder

    widths = launch_width_ladder(n)
    counts = sorted({0, 1, n, *widths, *(w + 1 for w in widths[1:]), *(w - 1 for w in widths)}, reverse=True)
    picked = [widths[int(launch_rung(np.int32(live), widths))] for live in counts]
    for live, width in zip(counts, picked):
        assert width >= live
        narrower = [w for w in widths if w < width]
        assert all(w < live for w in narrower)  # the narrowest that holds them
    assert picked == sorted(picked, reverse=True)  # rays only die: widths only shrink
    assert picked[0] == n and picked[-1] == widths[-1]  # live = 0: the narrowest rung


# -- the program -----------------------------------------------------------------


# frame_program's jitted closures, by what shapes the trace: the frame is an
# operand, so the cases that differ by the frame alone share a program. The
# ladder function, the unsort and the re-pack in force are part of the key: a
# patched one is another program, traced when first asked for, under that patch.
_FRAME_PROGRAMS: dict[tuple, object] = {}


def frame_program(scene_name, frame_index, *, size, samples, bounces):
    """(linear image, launches) of a whole frame."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    use_tlas, quant, builder, wide = integrator.resolve_bvh_config()

    def render(frame):
        return integrator.render_tile.__wrapped__(
            build_scene(scene_name, frame), scene_camera(scene_name, frame),
            frame, 0, 0, width=size, height=size, tile_height=size,
            tile_width=size, samples=samples, max_bounces=bounces,
            mesh=scene_mesh_set(scene_name, frame, builder, wide),
            use_tlas=use_tlas, quant=quant, with_live=True,
        )

    key = (
        scene_name, size, samples, bounces, integrator.launch_width_ladder,
        integrator._unsort, integrator._repack,
    )
    program = _FRAME_PROGRAMS.setdefault(key, jax.jit(render))
    image, launches = program(jnp.asarray(frame_index, jnp.float32))
    return np.asarray(image), None if launches is None else np.asarray(launches)


def region_program(scene_name, frame_index, *, size, samples, bounces):
    """Linear image of the lower right quarter, rendered as a region (its
    rays carry their full-frame RNG lanes), traced now."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    half = size // 2
    render = integrator._fused_region_renderer.__wrapped__(
        scene_name, size, size, half, half, samples, bounces,
        *integrator.resolve_bvh_config(),
    )
    return np.asarray(render(jnp.asarray(frame_index, jnp.float32), half, half)), None


def tile_sharded_program(scene_name, frame_index, *, size, samples, bounces):
    """Linear image of a frame of ``size`` x ``size // 2`` pixels rendered
    in two bands across the local mesh (each band its own ray set and
    ladder), built and traced now."""
    from tpu_render_cluster.parallel.sharded_render import sharded_frame_renderer

    sharded_frame_renderer.cache_clear()
    render = sharded_frame_renderer(
        scene_name, size, size // 2, samples, bounces, "tile", n_devices=2
    )
    image = np.asarray(render(frame_index))
    sharded_frame_renderer.cache_clear()
    return image, None


def two_steps_down(n: int) -> tuple[int, ...]:
    """The rungs a 512x512x8 settled frame takes (n, n/8, n/16), at a size
    the interpreter can afford."""
    return (n, n // 8, n // 16)


CASES = {
    # name: (scene, program, frame, size, bounces, ladder or None for the real one, widths expected or None)
    "settled": (DEEP_SCENE, frame_program, 295, 32, BOUNCES, None, [2048, 2048, 1024, 1024]),
    "falling": (DEEP_SCENE, frame_program, 30, 32, BOUNCES, None, [2048, 2048, 1024, 1024]),
    "falling_late": (DEEP_SCENE, frame_program, 150, 32, BOUNCES, None, [2048, 2048, 1024, 1024]),
    "region_with_rng_lanes": (DEEP_SCENE, region_program, 295, 64, BOUNCES, None, None),
    "no_ray_dies_early": (DEEP_SCENE, frame_program, 295, 32, 2, None, [2048, 2048]),
    "narrow_then_narrower": (DEEP_SCENE, frame_program, 295, 64, BOUNCES, two_steps_down, [8192, 8192, 1024, 512]),
    # two bands of 2048 rays, each narrowing by itself
    "tile_sharded": (DEEP_SCENE, tile_sharded_program, 295, 64, 3, None, None),
    # scenes whose program launches no per-bounce kernel: no ladder to read
    "shallow_mesh_has_no_ladder": ("02_physics-mesh", frame_program, 30, 32, BOUNCES, None, None),
    "sphere_scene_has_no_ladder": ("04_very-simple", frame_program, 104, 32, BOUNCES, None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_narrowed_program_renders_the_full_width_image(case, monkeypatch, interpreted_kernels):
    from tpu_render_cluster.render import integrator

    scene, program, frame, size, bounces, ladder, expected = CASES[case]
    if ladder is not None:
        monkeypatch.setattr(integrator, "launch_width_ladder", ladder)
    image, launches = program(scene, frame, size=size, samples=2, bounces=bounces)
    monkeypatch.setattr(integrator, "launch_width_ladder", one_rung)
    reference, full = program(scene, frame, size=size, samples=2, bounces=bounces)
    assert_the_same_image(image, reference)
    assert image.max() > 0.1 and image.std() > 0.01  # a picture, not a constant
    assert (launches is None) == (expected is None)
    if launches is not None:
        rays = size * size * 2
        assert launches.shape == (bounces, 2) and (full[:, 1] == rays).all()
        assert np.array_equal(launches[:, 0], full[:, 0])  # the same rays live and die
        assert launches[:, 1].tolist() == expected
        assert (launches[:, 0] <= launches[:, 1]).all()


def test_with_no_ray_left_the_narrowest_rung_returns_what_was_gathered(monkeypatch, interpreted_kernels):
    """Every ray leaves for the sky at the first bounce: live is 0 from the
    second on, the program takes the narrowest rung there, and the radiance
    of the first bounce comes back in place."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    n = 4096
    _tlas, quant, builder, wide = integrator.resolve_bvh_config()
    scene = build_scene(DEEP_SCENE, 295)
    mesh = scene_mesh_set(DEEP_SCENE, 295, builder, wide)
    spread = jax.random.uniform(jax.random.PRNGKey(5), (n, 3), minval=-0.3, maxval=0.3)
    directions = spread.at[:, 1].set(1.0)
    directions = directions / jnp.linalg.norm(directions, axis=1, keepdims=True)
    origins = jnp.tile(jnp.asarray([[0.0, 30.0, 0.0]], jnp.float32), (n, 1))

    def trace():
        launches = []
        radiance = integrator.trace_paths(
            scene, origins, directions, jax.random.PRNGKey(3), max_bounces=3,
            mesh=mesh, quant=quant, live_counts=launches,
        )
        return radiance, jnp.stack(launches)

    narrowest = integrator.launch_width_ladder(n)[-1]
    radiance, launches = jax.jit(trace)()
    monkeypatch.setattr(integrator, "launch_width_ladder", one_rung)
    reference, full = jax.jit(lambda: trace())()
    assert np.asarray(launches).tolist() == [[n, n], [0, narrowest], [0, narrowest]]
    assert np.asarray(full).tolist() == [[n, n], [0, n], [0, n]]
    assert_the_same_image(np.asarray(radiance), np.asarray(reference))
    assert float(jnp.min(radiance)) > 0.0  # the sky's, on every lane


# -- the unsort ------------------------------------------------------------------


def checked_against_the_scatter(unsort):
    """``unsort``, with every row whose bits differ from the scatter
    form's (``zeros.at[lane].set(radiance)``, the unsort until PR 31)
    replaced by NaN: both forms read the same state, in one program."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def checked(radiance, lane):
        by_sort = unsort(radiance, lane)
        by_scatter = jnp.zeros_like(radiance).at[lane].set(radiance)
        return jnp.where(bits(by_sort) == bits(by_scatter), by_sort, jnp.nan)

    return checked


def one_block_program(scene_name, frame_index, *, size, samples, bounces):
    """A whole frame of ``size`` x ``size`` at one sample: a ray set of
    one kernel block or less, whose ladder has one rung and whose program
    has no ``switch``."""
    from tpu_render_cluster.render import integrator

    assert integrator.launch_width_ladder(size * size) == (size * size,)
    return frame_program(scene_name, frame_index, size=size, samples=1, bounces=bounces)


UNSORT_CASES = {
    # name: (program, frame, size, bounces, ladder or None for the real one, widths expected or None)
    "whole_frame": (frame_program, 295, 32, BOUNCES, None, [2048, 2048, 1024, 1024]),
    "region_with_rng_lanes": (region_program, 295, 64, BOUNCES, None, None),
    "tile_sharded": (tile_sharded_program, 295, 64, 3, None, None),
    "one_rung_ray_set": (one_block_program, 295, 32, BOUNCES, None, [1024] * BOUNCES),
    "last_bounce_on_a_narrow_rung": (frame_program, 30, 64, BOUNCES, two_steps_down, [8192, 8192, 1024, 512]),
    # the last bounce at full width, behind a switch all the same
    "last_bounce_at_full_width": (frame_program, 295, 32, 2, None, [2048, 2048]),
}


@pytest.mark.parametrize("case", sorted(UNSORT_CASES))
def test_the_sort_puts_every_ray_where_the_scatter_put_it(case, monkeypatch, interpreted_kernels):
    """Bit for bit: not one row of the deep program's radiance differs
    from the same state unsorted by the scatter."""
    from tpu_render_cluster.render import integrator

    program, frame, size, bounces, ladder, expected = UNSORT_CASES[case]
    if ladder is not None:
        monkeypatch.setattr(integrator, "launch_width_ladder", ladder)
    monkeypatch.setattr(integrator, "_unsort", checked_against_the_scatter(integrator._unsort))
    image, launches = program(DEEP_SCENE, frame, size=size, samples=2, bounces=bounces)
    assert np.isfinite(image).all(), f"{np.isnan(image).any(axis=-1).sum()} pixels hold a ray the two forms place differently"
    assert image.max() > 0.1 and image.std() > 0.01  # a picture, not a constant
    assert (launches is None) == (expected is None)
    if launches is not None:
        assert launches[:, 1].tolist() == expected


def test_no_scatter_over_all_the_rays_is_left_in_the_deep_program(interpreted_kernels):
    """The unsort was the program's one ``scatter`` (set) of n rows, the
    dearest operation outside the kernels (PERF.md §6 PR 31). What may
    remain: the narrow rungs' ``scatter-add`` and the updates of a prefix."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    n = 4096
    _tlas, quant, builder, wide = integrator.resolve_bvh_config()
    scene = build_scene(DEEP_SCENE, 295)
    mesh = scene_mesh_set(DEEP_SCENE, 295, builder, wide)
    assert len(integrator.launch_width_ladder(n)) > 1

    def trace(origins, directions):
        return integrator.trace_paths(
            scene, origins, directions, jax.random.PRNGKey(3), max_bounces=BOUNCES,
            mesh=mesh, quant=quant, rng_lanes=jnp.arange(n)[::-1],
        )

    def equations(jaxpr):
        for equation in jaxpr.eqns:
            yield equation
            for inner in jax.core.jaxprs_in_params(equation.params):
                yield from equations(inner)

    rays = jax.ShapeDtypeStruct((n, 3), jnp.float32)
    found = list(equations(jax.make_jaxpr(trace)(rays, rays).jaxpr))
    rows = {}  # primitive name: the row counts of what it writes
    for equation in found:
        if equation.primitive.name.startswith("scatter"):
            rows.setdefault(equation.primitive.name, set()).add(equation.invars[2].aval.shape[0])
    assert n not in rows.get("scatter", ()), rows
    assert rows.get("scatter-add") and max(rows["scatter-add"]) < n, rows  # the narrow rungs'
    assert any(e.primitive.name == "sort" and len(e.invars) == 4 for e in found)  # the unsort itself


def test_the_unsort_is_the_scatter_under_vmap_too():
    """``render_frames_batched`` maps the deep program over a batch of
    frames: each frame's rows go back by its own lanes."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator

    frames, n = 3, 2048
    keys = jax.random.split(jax.random.PRNGKey(31), frames)
    lanes = jax.vmap(lambda key: jax.random.permutation(key, n))(keys).astype(jnp.int32)
    radiance = jax.random.uniform(keys[0], (frames, n, 3), minval=-4.0, maxval=4.0)
    by_sort = jax.jit(jax.vmap(integrator._unsort))(radiance, lanes)
    by_scatter = jax.vmap(lambda r, l: jnp.zeros_like(r).at[l].set(r))(radiance, lanes)
    assert by_sort.dtype == jnp.float32 and np.array_equal(np.asarray(by_sort), np.asarray(by_scatter))
    assert not np.array_equal(np.asarray(by_sort), np.asarray(radiance))
