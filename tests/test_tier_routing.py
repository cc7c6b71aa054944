"""Which program the tpu-raytrace backend runs for a work unit.

There is one way to render; the backend chooses the program's shape from
what it can observe: a tile unit is a `region`, a whole frame is `sharded`
where the worker shards across its local mesh and `masked` everywhere
else. Neither the scene nor what else the worker's queue holds changes
that, and the choice never builds the scene's mesh set on the host (the
backend names the program's trace kernel from the set's SHAPES, asked for
under `jax.eval_shape`: nothing is built, and that call is not counted).
"""

from __future__ import annotations

import asyncio
import dataclasses

import pytest

from tests.test_steps import SenderStub, make_job

SCENES = {
    "sphere": "04_very-simple",
    "shallow": "02_physics-mesh",
    "deep": "03_physics-2-mesh",
}
TIERS = ("masked", "region", "sharded")


@pytest.fixture
def routed(monkeypatch):
    """Pallas on, the three renderer factories replaced by recorders, and
    every host-side build of a mesh set counted."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.parallel import sharded_render
    from tpu_render_cluster.render import integrator, mesh

    monkeypatch.setenv("TRC_PALLAS", "1")
    seen = {"rendered": [], "mesh_sets": 0}
    shapes_of_mesh_set = mesh.scene_mesh_set

    def mesh_set(scene_name, frame, *args, **kwargs):
        if isinstance(frame, jax.core.Tracer):
            return shapes_of_mesh_set(scene_name, frame, *args, **kwargs)
        seen["mesh_sets"] += 1

    def masked(*_args, **_kwargs):
        def render(_frame):
            seen["rendered"].append("masked")
            return jnp.zeros((8, 8, 3), jnp.uint8), None  # the image, no live counts
        return render

    def region(_scene, _width, _height, tile_height, tile_width, *_args, **_kwargs):
        def render(_frame, _y0, _x0):
            seen["rendered"].append("region")
            return jnp.zeros((tile_height, tile_width, 3), jnp.float32)
        return render

    def sharded(*_args, **_kwargs):
        def render(_frame):
            seen["rendered"].append("sharded")
            return jnp.zeros((8, 8, 3), jnp.float32)
        return render

    monkeypatch.setattr(mesh, "scene_mesh_set", mesh_set)
    monkeypatch.setattr(integrator, "fused_frame_renderer", masked)
    monkeypatch.setattr(integrator, "fused_region_renderer", region)
    monkeypatch.setattr(sharded_render, "sharded_frame_renderer", sharded)
    return seen


def backend_with(tmp_path, sharding=None):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    return TpuRaytraceBackend(
        base_directory=tmp_path, width=8, height=8, samples=1, max_bounces=2,
        sharding=sharding,
    )


def tier_counts(backend) -> dict[str, float]:
    return {tier: backend._tier_frames.value(tier=tier) for tier in TIERS}


def serve(backend, job, units: list[tuple[int, int | None]]) -> None:
    """The units through a worker's own queue, all queued before the first
    is rendered: the later ones wait behind it, as under a batching strategy."""
    from tpu_render_cluster.obs import MetricsRegistry
    from tpu_render_cluster.traces.worker_trace import WorkerTraceBuilder
    from tpu_render_cluster.utils.cancellation import CancellationToken
    from tpu_render_cluster.worker.queue import WorkerAutomaticQueue

    async def drive():
        queue = WorkerAutomaticQueue(
            backend, SenderStub(), WorkerTraceBuilder(), CancellationToken(),
            metrics=MetricsRegistry(),
        )
        for frame, tile in units:
            queue.queue_frame(job, frame, tile=tile)
        queue.start()
        while queue.queue_size():
            await asyncio.sleep(0.005)
        await queue.join()

    asyncio.run(drive())


@pytest.mark.parametrize("unit", ["frame", "tile"])
@pytest.mark.parametrize("frames_ahead", [0, 4])
@pytest.mark.parametrize("scene", sorted(SCENES))
def test_the_tier_follows_the_unit_and_never_the_scene_or_the_queue(
    scene, frames_ahead, unit, routed, tmp_path
):
    backend = backend_with(tmp_path)
    job = make_job(f"{SCENES[scene]}_routing", 8)
    tile = None
    if unit == "tile":
        job, tile = dataclasses.replace(job, tile_grid=(2, 2)), 1
    expected = "region" if unit == "tile" else "masked"
    backend.warm(SCENES[scene])  # the whole-frame program, whatever units follow
    assert routed["rendered"] == ["masked"]
    before = tier_counts(backend)
    units = [(frame, tile) for frame in range(1, frames_ahead + 2)]
    serve(backend, job, units)
    assert routed["rendered"][1:] == [expected] * len(units)
    after = tier_counts(backend)
    assert {tier: after[tier] - before[tier] for tier in TIERS} == {
        tier: (len(units) if tier == expected else 0) for tier in TIERS
    }
    assert routed["mesh_sets"] == 0
    assert len(list((tmp_path / "out").iterdir())) == len(units)


@pytest.mark.parametrize("sharding", ["spp", "tile"])
@pytest.mark.parametrize("scene", sorted(SCENES))
def test_a_sharding_worker_shards_whole_frames_and_only_those(scene, sharding, routed, tmp_path):
    """Local tile/spp sharding serves a whole frame; a cluster tile is
    already sub-frame work and takes the region program there too."""
    backend = backend_with(tmp_path, sharding=sharding)
    job = dataclasses.replace(make_job(f"{SCENES[scene]}_routing", 8), tile_grid=(2, 2))
    backend.warm(SCENES[scene])
    before = tier_counts(backend)
    serve(backend, job, [(1, None), (1, 2), (2, None)])
    assert routed["rendered"] == ["sharded", "sharded", "region", "sharded"]
    after = tier_counts(backend)
    assert {tier: after[tier] - before[tier] for tier in TIERS} == {
        "masked": 0, "region": 1, "sharded": 2,
    }
    assert routed["mesh_sets"] == 0


# -- which kernel a frame's program reaches ----------------------------------------------

KERNEL_OF = {
    "sphere": "_trace_fused",
    "shallow": "_trace_fused_mesh",
    "deep": "_mesh_bounce_io",
}


def test_three_pallas_call_sites_and_a_frame_program_reaches_exactly_one(monkeypatch):
    """``pallas_kernels.py`` holds three ``pl.pallas_call(`` and the traced
    frame program of each scene class launches from one of them only: no
    kernel but the three that are served, and no scene served by two."""
    import inspect
    import sys

    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator, pallas_kernels

    source = inspect.getsource(pallas_kernels)
    assert source.count("pl.pallas_call(") == 3

    sites = []
    launch = pallas_kernels.pl.pallas_call

    def counted(*args, **kwargs):
        sites.append(sys._getframe(1).f_code.co_name)
        return launch(*args, **kwargs)

    monkeypatch.setenv("TRC_PALLAS", "1")
    monkeypatch.setattr(pallas_kernels.pl, "pallas_call", counted)
    for scene in sorted(SCENES):
        jax.clear_caches()  # the wrappers are jitted: trace them anew
        integrator.fused_frame_renderer.cache_clear()
        sites.clear()
        render = integrator.fused_frame_renderer(SCENES[scene], 16, 16, 1, 2)
        jax.make_jaxpr(render)(jnp.float32(1))
        assert sites and set(sites) == {KERNEL_OF[scene]}, (scene, sites)
    integrator.fused_frame_renderer.cache_clear()
    jax.clear_caches()


def test_the_reference_walks_hold_no_kernel_whatever_the_environment_says(monkeypatch):
    """With ``TRC_PALLAS=1`` the XLA walks still trace to XLA alone: what
    the kernels are compared with cannot turn into a kernel behind a
    test's back."""
    import jax
    import jax.numpy as jnp

    from tests.test_scan_stream import pallas_calls
    from tpu_render_cluster.render import geometry, mesh, pallas_kernels
    from tpu_render_cluster.render.scene import build_scene

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    assert pallas_kernels.pallas_enabled()
    rays = jnp.ones((64, 3), jnp.float32)
    scene = build_scene(SCENES["deep"], 1)
    mesh_set = mesh.scene_mesh_set(SCENES["deep"], 1)
    already = jnp.zeros((64,), bool)
    for walk, args in (
        (geometry.intersect_spheres, (scene, rays, rays)),
        (geometry.occluded_sun, (scene, rays, rays)),
        (mesh.intersect_instances, (*mesh_set, rays, rays)),
        (mesh.occluded_instances, (*mesh_set, rays, rays, already)),
    ):
        jaxpr = jax.make_jaxpr(walk)(*args)
        assert not list(pallas_calls(jaxpr.jaxpr)), walk.__name__
    jax.clear_caches()
