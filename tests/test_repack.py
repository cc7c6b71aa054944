"""The deep path's full-width re-pack moves a ray's state by sorts, not by
an argsort and gathers (ISSUE 55).

``integrator._repack(order, arrays)`` is ``array[order]`` for a permutation
``order``: the function alone on keys with many ties and a dead tail, the
deep program with it against the same program with the gathers put back,
what is left in the program's HLO, and the counter that says how a
launch's rays were put in its order. Pallas interpreter on the CPU, tiny
frames; the programs come from ``tests/test_launch_width.py``.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

from tests.test_launch_width import (  # noqa: F401  (interpreted_kernels is a fixture)
    BOUNCES,
    DEEP_SCENE,
    frame_program,
    interpreted_kernels,
    one_rung,
    region_program,
    two_steps_down,
)


def by_gathers(order, arrays):
    """The re-pack as it was until PR 55: the [n, 3] arrays packed into
    one [n, 3k] gather, each lane a gather of its own."""
    import jax.numpy as jnp

    packed = jnp.concatenate([a for a in arrays if a.ndim == 2], axis=1)[order]
    columns = iter(packed[:, i:i + 3] for i in range(0, packed.shape[1], 3))
    return [next(columns) if a.ndim == 2 else a[order] for a in arrays]


def bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


# -- the function ----------------------------------------------------------------


@pytest.mark.parametrize("mapped", ["one_ray_set", "under_vmap"])
@pytest.mark.parametrize("columns", [2, 4], ids=["bounce_0s_six_columns", "twelve_columns"])
@pytest.mark.parametrize("rng", [False, True], ids=["lane_alone", "lane_and_rng"])
def test_the_sorted_repack_is_the_argsort_and_the_gathers(rng, columns, mapped):
    """Exactly: every row of every array where ``array[argsort(keys)]``
    puts it, bit for bit, on keys of which most are tied and a third
    carry the dead flag (so the order's tail is the dead rays)."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.pallas_kernels import KEY_DEAD_BIT

    sets, n = (3, 3000) if mapped == "under_vmap" else (1, 5000)
    root = jax.random.PRNGKey(55)

    def ray_set(key):
        ks = jax.random.split(key, 8)
        dead = jax.random.uniform(ks[0], (n,)) < 1 / 3
        keys = jax.random.randint(ks[1], (n,), 0, 40) | (dead.astype(jnp.int32) << KEY_DEAD_BIT)
        lanes = [jax.random.permutation(k, n).astype(jnp.int32) for k in ks[2:4]]
        arrays = [jax.random.normal(k, (n, 3)) * 1e3 for k in ks[4:4 + columns]]
        # bit patterns a sort's payload must carry as they are
        arrays[0] = arrays[0].at[:5, 0].set(jnp.asarray([-0.0, jnp.inf, -jnp.inf, 1e-42, 0.0]))
        return keys, [*lanes[: 2 if rng else 1], *arrays]

    keys, arrays = jax.vmap(ray_set)(jax.random.split(root, sets))

    def both(keys, arrays):
        order = jnp.argsort(keys)
        return integrator._repack(order, arrays), by_gathers(order, arrays), keys[order]

    by_sorts, gathered, sorted_keys = jax.jit(jax.vmap(both))(keys, arrays)
    assert len(by_sorts) == len(arrays) == (2 if rng else 1) + columns
    for moved, reference, array in zip(by_sorts, gathered, arrays):
        assert moved.dtype == array.dtype and moved.shape == array.shape
        assert np.array_equal(bits(moved), bits(reference))
        assert not np.array_equal(bits(moved), bits(array))  # something moved
    sorted_keys = np.asarray(sorted_keys)
    assert (np.diff(sorted_keys, axis=1) >= 0).all()
    dead = sorted_keys >> KEY_DEAD_BIT
    assert (dead[:, : n // 2] == 0).all() and (dead[:, -n // 4:] == 1).all()  # the live first, a dead tail
    assert len(np.unique(sorted_keys)) <= 80  # ties by the hundred


# -- the program -----------------------------------------------------------------


REPACK_CASES = {
    # name: (program, frame, size, samples, ladder or None for the real one, widths expected or None)
    "one_rung": (frame_program, 295, 32, 2, one_rung, [2048] * BOUNCES),
    "two_rungs": (frame_program, 30, 32, 2, None, [2048, 2048, 1024, 1024]),
    "narrow_then_narrower": (frame_program, 295, 64, 2, two_steps_down, [8192, 8192, 1024, 512]),
    # 16,384 rays: the smallest ray set whose ladder has all four rungs
    "four_rungs": (frame_program, 295, 64, 4, None, [16384, 16384, 2048, 1024]),
    "region_with_rng_lanes": (region_program, 295, 64, 2, None, None),
}


@pytest.mark.parametrize("case", sorted(REPACK_CASES))
def test_the_deep_frame_is_the_frame_of_the_gathers_bit_for_bit(case, monkeypatch, interpreted_kernels):
    """The same permutation of the same bits: a frame of the program whose
    full-width bounces re-pack by sorts equals, in every bit of its linear
    image, the frame of the same program with the gathers put back."""
    from tpu_render_cluster.render import integrator

    program, frame, size, samples, ladder, expected = REPACK_CASES[case]
    if ladder is not None:
        monkeypatch.setattr(integrator, "launch_width_ladder", ladder)
    assert case != "four_rungs" or len(integrator.launch_width_ladder(size * size * samples)) == 4
    image, launches = program(DEEP_SCENE, frame, size=size, samples=samples, bounces=BOUNCES)
    monkeypatch.setattr(integrator, "_repack", by_gathers)
    reference, gathered = program(DEEP_SCENE, frame, size=size, samples=samples, bounces=BOUNCES)
    assert image.dtype == np.float32 and np.array_equal(bits(image), bits(reference))
    assert image.max() > 0.1 and image.std() > 0.01  # a picture, not a constant
    assert (launches is None) == (expected is None)
    if launches is not None:
        assert np.array_equal(launches, gathered)  # the same rays live and die
        assert launches[:, 1].tolist() == expected


def test_no_gather_over_all_the_rays_is_left_in_the_deep_program(monkeypatch, interpreted_kernels):
    """The two full-width re-packs were the program's dearest operations
    outside the kernels: a packed ``[n, 12]`` gather and a gather a lane,
    each paying for every row (PERF.md §6 PR 55). What may remain: the
    narrow rungs' gathers of their first ``width`` rows. With the gathers
    put back the same search finds them."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    n = 4096
    _tlas, quant, builder, wide = integrator.resolve_bvh_config()
    scene = build_scene(DEEP_SCENE, 295)
    mesh = scene_mesh_set(DEEP_SCENE, 295, builder, wide)
    widths = integrator.launch_width_ladder(n)
    assert len(widths) > 1

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

    def gathered_rows(program):
        """Row counts of what the ``resort`` scopes' gathers produce."""
        rays = jax.ShapeDtypeStruct((n, 3), jnp.float32)
        return {
            equation.outvars[0].aval.shape[0]
            for equation in equations(jax.make_jaxpr(program)(rays, rays).jaxpr)
            if equation.primitive.name == "gather" and "resort" in str(equation.source_info.name_stack)
        }

    rows = gathered_rows(trace)
    # the narrow rungs' (and the 48 instances' boxes, for the first keys), and none of n rows
    assert widths[1] in rows and max(rows) < n, rows
    compiled = jax.jit(trace).lower(
        jax.ShapeDtypeStruct((n, 3), jnp.float32), jax.ShapeDtypeStruct((n, 3), jnp.float32)
    ).compile().as_text()
    # the compiled program (XLA:CPU's here): its gathers by the rows of their results
    compiled_rows = {int(rows) for rows in re.findall(r"= \w+\[(\d+)[^=]* gather\(", compiled)}
    assert widths[1] in compiled_rows and n not in compiled_rows, compiled_rows
    assert f"f32[{n},12]" not in compiled
    monkeypatch.setattr(integrator, "_repack", by_gathers)
    # a function of its own: the same one would be handed its first trace again
    assert n in gathered_rows(lambda origins, directions: trace(origins, directions))


# -- the counter -----------------------------------------------------------------


@pytest.fixture
def fresh_registry(monkeypatch):
    from tpu_render_cluster import obs
    from tpu_render_cluster.obs import MetricsRegistry

    monkeypatch.setattr(obs, "_global_registry", MetricsRegistry())
    return obs.get_registry


def repacks() -> dict[str, float]:
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    counter = TpuRaytraceBackend._repacks_counter()
    return {by: counter.value(by=by) for by in ("sort", "gather")}


def test_both_labels_read_zero_on_a_fresh_workers_scrape(fresh_registry):
    from tpu_render_cluster.obs.prometheus import lint_metric, render_prometheus
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    TpuRaytraceBackend(width=8, height=8, samples=1, max_bounces=2)
    text = render_prometheus(fresh_registry().snapshot())  # refuses a name that fails the lint
    for by in ("sort", "gather"):
        assert f'render_bounce_repacks_total{{by="{by}"}} 0' in text
    assert lint_metric("render_bounce_repacks_total", "counter", ("by",)) == []


@pytest.mark.parametrize("launches,counted", [
    # the mesh cells' frames: n, n, n/8, n/16
    ([[2097152, 2097152], [1803214, 2097152], [214411, 262144], [101203, 131072]], {"sort": 2, "gather": 2}),
    ([[2048, 2048], [256, 1024]], {"sort": 1, "gather": 1}),
    # no ray dies early: every launch at the widest rung
    ([[1024, 1024]] * 4, {"sort": 4, "gather": 0}),
])
def test_a_launch_at_the_widest_rung_counts_as_a_sort_and_a_narrower_one_as_a_gather(launches, counted, fresh_registry):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    TpuRaytraceBackend._observe_launches(np.asarray(launches, np.int32))
    assert repacks() == counted
    TpuRaytraceBackend._observe_launches(np.asarray(launches, np.int32))
    assert repacks() == {by: 2 * count for by, count in counted.items()}
    assert TpuRaytraceBackend._launched_lanes_counter().value() == 2 * sum(width for _, width in launches)


@pytest.mark.parametrize("scene,counted", [
    (DEEP_SCENE, {"sort": 2, "gather": 2}), ("04_very-simple", {"sort": 0, "gather": 0}),
])
def test_a_served_frame_counts_its_launches(scene, counted, tmp_path, interpreted_kernels):
    """Through the backend: a deep frame's four launches ran at 2048, 2048,
    1024, 1024 (the program's own report), so two got their rays by a sort
    and two by a gather; a sphere frame launches no bounce and counts none."""
    from tests.test_steps import make_job
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    backend = TpuRaytraceBackend(
        base_directory=tmp_path, width=32, height=32, samples=2, max_bounces=BOUNCES,
    )
    before = repacks()
    backend._render_sync(make_job(f"{scene}_steps", 4), 1)
    after = repacks()
    assert {by: after[by] - before[by] for by in after} == counted
