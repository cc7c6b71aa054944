"""The shallow mesh job (ISSUE 56; configuration `02phmesh-240f-1w`, cell
`02phmesh-1w-queued`): 24 tumbling boxes over a 3-node BLAS, the one
family the mesh megakernel traces, from its first frames.

On the CPU, Pallas interpreter, small shapes:

- the family through the `tpu-raytrace` backend's normal path (files as a
  worker writes them) against `benchmark/reference/plain_tracer.py` by the
  check's own `independent_agreement`, on a frame of bodies falling and on
  one of bodies at rest; the bodies left out fail that check, and the
  kernels' contractions rounded to bf16 fail the same-stream limits
  against the sound frames;
- the megakernel against `_trace_paths_deep` on the same rays;
- which trace kernel a frame's program holds, said once:
  `integrator.trace_kernel_name` is what `trace_paths` dispatches by (one
  case a road, lane ids included), every family counts its frames under
  its own `kernel` label of `render_trace_kernel_frames_total` and no
  other, all labels at 0 before a frame, the `dispatch` step's event, the
  program's `render.compile` spans and the backend's `trace_kernels` say
  the same name;
- one frame of each family is byte for byte the picture the parent's
  dispatch (its chain of `if`s, written out here) gives;
- the configuration's data: the checked frames are 32 and 36 whatever the
  first frame, and the backlog rule's two cases.
"""

from __future__ import annotations

import dataclasses
import functools
import types

import numpy as np
import pytest

from benchmark.lib import check, manifest
from benchmark.reference import plain_tracer
from benchmark.tests import test_backlog_rule as rule
from tests.test_scan_stream import scene_arrays  # what `benchmark/lib/region_child.py` hands the reference
from tests.test_steps import make_job
from tests.test_tier_routing import serve

CONFIG, CELL = "02phmesh-240f-1w", "02phmesh-1w-queued"
SCENE = "02_physics-mesh"
SIZE = 64
FALLING, AT_REST = 32, 230  # a frame the cell's check reads; every body landed
FAMILY_KERNELS = {
    "04_very-simple": "sphere_fused",
    "02_physics-mesh": "mesh_fused",
    "03_physics-2-mesh": "mesh_bounce",
    "03_physics-2-scan": "mesh_stream",
}


@pytest.fixture
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("TRC_PALLAS", "1")


def fresh_programs():
    import jax

    from tpu_render_cluster.render import integrator

    integrator.fused_frame_renderer.cache_clear()
    integrator.fused_region_renderer.cache_clear()
    jax.clear_caches()  # a kernel's trace is cached by the function it wraps, not by what that calls


# -- the family through the backend, against the independent reference -------------------


def rendered_by_the_backend(base, frames) -> dict[int, np.ndarray]:
    """The cell's job at 64x64 through the backend's one-frame path: the
    JPEG files as a worker writes them, decoded."""
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    shape = manifest.load_cell(CELL).config["render"]
    backend = TpuRaytraceBackend(
        base_directory=base, width=SIZE, height=SIZE, samples=shape["samples"], max_bounces=shape["max_bounces"],
    )
    job = make_job("02_physics-mesh_240f-1w", 240)
    pictures = {}
    for frame in frames:
        backend._render_sync(job, frame)
        pictures[frame] = check.load_rgb(base / "out" / f"rendered-{frame:05d}.jpg")
    assert set(backend.trace_kernels.values()) == {"mesh_fused"}
    return pictures


@pytest.fixture(scope="module")
def sound_frames(tmp_path_factory):
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TRC_PALLAS", "1")
        fresh_programs()
        return rendered_by_the_backend(tmp_path_factory.mktemp("sound"), (FALLING, AT_REST))


@pytest.fixture(scope="module")
def references():
    """`plain_tracer`'s replicas of the whole 64x64 frame, as
    `check.check_images` asks for them, on both frames."""
    cell = manifest.load_cell(CELL)
    shape, independent = cell.config["render"], cell.config["check"]["independent"]
    assert independent["reference"] == "plain_tracer" and independent["scene_is_static"] is False
    return {
        frame: plain_tracer.render_crop_replicas(
            *scene_arrays(SCENE, frame), width=SIZE, height=SIZE, y0=0, x0=0, size=SIZE,
            samples=shape["samples"], max_bounces=shape["max_bounces"],
            replicas=independent["replicas"], seed=check.mix(frame),
        )
        for frame in (FALLING, AT_REST)
    }


def independent_verdict(served: np.ndarray, replicas: np.ndarray) -> tuple[bool, float]:
    independent = manifest.load_cell(CELL).config["check"]["independent"]
    return check.independent_agreement(
        served, replicas, block=independent["block"], sigmas=independent["sigmas"], abs_levels=independent["abs_levels"],
    )


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("frame", [FALLING, AT_REST])
def test_the_backends_frame_agrees_with_the_independent_reference(frame, sound_frames, references):
    served = sound_frames[frame]
    assert served.shape == (SIZE, SIZE, 3) and served.std() > 5.0
    ok, excess = independent_verdict(served, references[frame])
    assert ok, f"frame {frame}: a block mean lies {excess:.2f} levels beyond the reference's own spread"


@pytest.mark.time_limit(600)
def test_the_bodies_left_out_fail_the_independent_check_on_both_frames(
    interpreted_kernels, monkeypatch, tmp_path, references
):
    from tpu_render_cluster.render import scene as scene_module

    build = scene_module.build_mesh_instances

    def under_the_floor(name, frame):
        instances = build(name, frame)
        return instances._replace(translation=instances.translation + np.array([0.0, -1.0e4, 0.0], np.float32))

    monkeypatch.setattr(scene_module, "build_mesh_instances", under_the_floor)
    fresh_programs()
    try:
        control = rendered_by_the_backend(tmp_path, (FALLING, AT_REST))
    finally:
        monkeypatch.undo()
        fresh_programs()
    for frame, served in control.items():
        ok, excess = independent_verdict(served, references[frame])
        assert not ok and excess > 5.0, f"frame {frame}: without its boxes the picture lies {excess:.2f} levels out"


@pytest.mark.time_limit(600)
def test_contractions_rounded_to_bf16_fail_the_same_stream_limits_on_both_frames(
    interpreted_kernels, monkeypatch, tmp_path, sound_frames
):
    """The kernels' contractions in one ROUNDED bf16 pass (the nearest
    precision below the float32 the configuration states) against the
    sound program's files of the same frames, by the configuration's own
    `max_levels` and `min_share` on the frame's interior: the sound side
    against itself agrees everywhere, the control has to fail."""
    import jax
    import jax.numpy as jnp

    from tpu_render_cluster.render import pallas_kernels

    same = manifest.load_cell(CELL).config["check"]["same_stream"]

    def rounded_parts(x):
        hi = x.astype(jnp.bfloat16)
        return hi, jnp.zeros_like(hi), jnp.zeros_like(hi)

    def rounded_dot(a, b, dimension_numbers):
        a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
        return jax.lax.dot_general(a, b, dimension_numbers, preferred_element_type=jnp.float32)

    monkeypatch.setattr(pallas_kernels, "_bf16_parts", rounded_parts)
    monkeypatch.setattr(pallas_kernels, "_dot_f32", rounded_dot)
    fresh_programs()
    try:
        control = rendered_by_the_backend(tmp_path, (FALLING, AT_REST))
    finally:
        monkeypatch.undo()
        fresh_programs()
    agreement = functools.partial(
        check.same_stream_agreement, y0=0, x0=0, border=same["border"], max_levels=same["max_levels"], quality=None,
    )
    for frame, served in control.items():
        assert agreement(sound_frames[frame], sound_frames[frame]) == 1.0
        share = agreement(served, sound_frames[frame])
        assert share < same["min_share"] - 0.04, f"frame {frame}: the control agrees on {share:.3f}"


# -- the megakernel against one bounce kernel a bounce ------------------------------------


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("frame", [FALLING, AT_REST])
def test_the_megakernel_and_the_bounce_kernels_trace_the_same_paths(frame, interpreted_kernels):
    """The same rays, seed and per-lane streams down both roads: radiance
    equal to rounding (a path that flips at a float tie is a ray or two)."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    size, samples, bounces = 32, 2, 4
    scene, camera, mesh = build_scene(SCENE, frame), scene_camera(SCENE, frame), scene_mesh_set(SCENE, frame)
    base_key = integrator.tile_base_key(jnp.float32(frame), 0, 0)
    origins, directions = integrator.flat_sample_rays(
        camera, base_key, width=size, height=size, y0=0, x0=0, tile_height=size, tile_width=size, samples=samples,
    )
    key = integrator.tile_trace_key(base_key)
    assert integrator.trace_kernel_name(mesh) == "mesh_fused"
    fused = np.asarray(integrator.trace_paths(scene, origins, directions, key, max_bounces=bounces, mesh=mesh))
    deep = np.asarray(integrator._trace_paths_deep(
        scene, mesh, origins, directions, integrator.trace_seed(key), max_bounces=bounces,
        rng_lanes=None, use_tlas=None, quant=None, live_counts=None,
    ))
    assert fused.shape == deep.shape == (samples * size * size, 3) and fused.std() > 0.05
    apart = np.abs(fused - deep).max(axis=-1)
    assert (apart <= 1e-4).mean() >= 0.995, f"{(apart > 1e-4).sum()} rays apart, the furthest by {apart.max():.4f}"


# -- which kernel traced a frame, said once ------------------------------------------------


class Recorded(Exception):
    """Raised by a road's stand-in: the road `trace_paths` took."""


def a_mesh(nodes: int, instances: int, streamed: bool = False):
    """What `trace_kernel_name` reads of a mesh set, and nothing else."""
    return types.SimpleNamespace(
        bvh=types.SimpleNamespace(stream=object() if streamed else None, skip=np.zeros(nodes, np.int32)),
        instances=types.SimpleNamespace(translation=np.zeros((instances, 3), np.float32)),
    )


ROADS = [
    # mesh, lane ids given, Pallas on: the name
    (None, False, True, "sphere_fused"),
    (None, True, True, "sphere_fused"),
    (a_mesh(3, 24), False, True, "mesh_fused"),
    (a_mesh(3, 24), True, True, "mesh_bounce"),  # a region of the shallow family hands its lanes over
    (a_mesh(127, 48), False, True, "mesh_bounce"),
    (a_mesh(32, 32), False, True, "mesh_fused"),  # 1,024: the gate's last
    (a_mesh(33, 32), False, True, "mesh_bounce"),
    (a_mesh(3, 24, streamed=True), False, True, "mesh_stream"),  # streamed: never the megakernel, however shallow
    (a_mesh(127, 48, streamed=True), True, True, "mesh_stream"),
    (a_mesh(3, 24), False, False, "xla_loop"),
    (None, False, False, "xla_loop"),
]


@pytest.mark.parametrize("mesh,lanes,pallas,name", ROADS)
def test_trace_paths_takes_the_road_the_name_function_names(mesh, lanes, pallas, name, monkeypatch):
    from tpu_render_cluster.render import integrator, pallas_kernels

    assert name in integrator.TRACE_KERNELS
    monkeypatch.setattr(pallas_kernels, "pallas_enabled", lambda: pallas)

    def road(taken):
        def stand_in(*_args, **kwargs):
            raise Recorded(taken, kwargs.get("lane") is not None or kwargs.get("rng_lanes") is not None)
        return stand_in

    monkeypatch.setattr(pallas_kernels, "trace_paths_fused", road("sphere_fused"))
    monkeypatch.setattr(pallas_kernels, "trace_paths_fused_mesh", road("mesh_fused"))
    monkeypatch.setattr(integrator, "_trace_paths_deep", road("deep"))
    monkeypatch.setattr(integrator, "_shade_bounce", road("xla_loop"))
    rng_lanes = np.arange(8, dtype=np.int32) if lanes else None
    assert integrator.trace_kernel_name(mesh, rng_lanes) == name
    rays = np.zeros((8, 3), np.float32)
    with pytest.raises(Recorded) as taken:
        integrator.trace_paths(None, rays, rays, np.zeros(2, np.uint32), max_bounces=1, mesh=mesh, rng_lanes=rng_lanes)
    road_taken, lanes_handed_on = taken.value.args
    assert road_taken == {"mesh_bounce": "deep", "mesh_stream": "deep"}.get(name, name)
    # the lane ids go where the road takes them: both megakernels' roads but the mesh one, which is never given any
    assert lanes_handed_on == (lanes and pallas)


def test_the_gate_is_the_parents():
    from tpu_render_cluster.render import pallas_kernels

    assert pallas_kernels.MESH_MEGAKERNEL_MAX_WALK == 1024
    assert pallas_kernels.mesh_megakernel_eligible(a_mesh(32, 32))
    assert not pallas_kernels.mesh_megakernel_eligible(a_mesh(1025, 1))
    assert not pallas_kernels.mesh_megakernel_eligible(a_mesh(1, 1, streamed=True))
    assert pallas_kernels.tlas_enabled() is True  # the default every cell runs


@pytest.fixture
def recorded_renderers(monkeypatch):
    """Pallas on and the two renderer factories replaced by recorders (as
    `tests/test_tier_routing.py` does): what is under test is what the
    backend says of a program, not the program."""
    import jax.numpy as jnp

    from tpu_render_cluster import obs
    from tpu_render_cluster.render import integrator

    monkeypatch.setenv("TRC_PALLAS", "1")
    monkeypatch.setattr(obs, "_global_registry", obs.MetricsRegistry())

    def masked(*_args, **_kwargs):
        return lambda _frame: (jnp.zeros((8, 8, 3), jnp.uint8), None)

    def region(_scene, _width, _height, tile_height, tile_width, *_args, **_kwargs):
        return lambda _frame, _y0, _x0: jnp.zeros((tile_height, tile_width, 3), jnp.float32)

    monkeypatch.setattr(integrator, "fused_frame_renderer", masked)
    monkeypatch.setattr(integrator, "fused_region_renderer", region)


def kernel_counts(backend) -> dict[str, float]:
    from tpu_render_cluster.render.integrator import TRACE_KERNELS

    return {kernel: backend._kernel_frames.value(kernel=kernel) for kernel in TRACE_KERNELS}


def small_backend(tmp_path):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    return TpuRaytraceBackend(base_directory=tmp_path, width=8, height=8, samples=1, max_bounces=2)


def test_every_label_is_exposed_at_zero_before_any_frame(recorded_renderers, tmp_path):
    from tpu_render_cluster import obs
    from tpu_render_cluster.obs.prometheus import lint_metric, render_prometheus
    from tpu_render_cluster.render.integrator import TRACE_KERNELS

    backend = small_backend(tmp_path)
    assert set(FAMILY_KERNELS.values()) | {"xla_loop"} == set(TRACE_KERNELS)
    assert kernel_counts(backend) == dict.fromkeys(TRACE_KERNELS, 0.0) and backend.trace_kernels == {}
    text = render_prometheus(obs.get_registry().snapshot())  # refuses a name that fails the lint
    for kernel in TRACE_KERNELS:
        assert f'render_trace_kernel_frames_total{{kernel="{kernel}"}} 0' in text
    assert lint_metric("render_trace_kernel_frames_total", "counter", ("kernel",)) == []


@pytest.mark.parametrize("family", sorted(FAMILY_KERNELS))
def test_a_familys_frames_count_under_its_own_kernel_and_no_other(family, recorded_renderers, tmp_path):
    from tpu_render_cluster.render.integrator import scene_trace_kernel

    expected = FAMILY_KERNELS[family]
    assert scene_trace_kernel(family) == expected
    backend = small_backend(tmp_path)
    backend.warm(family)  # a warm frame is nobody's frame: built, named, not counted
    assert sum(kernel_counts(backend).values()) == 0.0
    assert backend.trace_kernels == {f"{family}@8x8x1x2 masked": expected}
    serve(backend, make_job(f"{family}_kernels", 8), [(frame, None) for frame in (1, 2, 3)])
    assert kernel_counts(backend) == {kernel: (3.0 if kernel == expected else 0.0) for kernel in kernel_counts(backend)}
    assert backend._tier_frames.value(tier="masked") == 3.0


def test_a_tile_of_the_shallow_family_counts_under_the_bounce_kernel(recorded_renderers, tmp_path):
    """A region hands its rays' full-frame lane ids over, and the mesh
    megakernel takes none: the same family, another kernel, and the
    backend says so program by program."""
    backend = small_backend(tmp_path)
    job = dataclasses.replace(make_job(f"{SCENE}_kernels", 8), tile_grid=(2, 2))
    serve(backend, job, [(1, 0), (1, 1)])
    serve(backend, dataclasses.replace(job, tile_grid=None), [(2, None)])
    counts = kernel_counts(backend)
    assert (counts["mesh_bounce"], counts["mesh_fused"], sum(counts.values())) == (2.0, 1.0, 3.0)
    assert backend.trace_kernels == {f"{SCENE}@8x8x1x2 region": "mesh_bounce", f"{SCENE}@8x8x1x2 masked": "mesh_fused"}


def test_without_pallas_a_frame_counts_under_the_xla_loop(recorded_renderers, monkeypatch, tmp_path):
    monkeypatch.delenv("TRC_PALLAS")
    backend = small_backend(tmp_path)
    serve(backend, make_job(f"{SCENE}_kernels", 8), [(1, None)])
    assert kernel_counts(backend)["xla_loop"] == 1.0 and sum(kernel_counts(backend).values()) == 1.0


@pytest.mark.time_limit(600)
def test_the_dispatch_step_the_compile_spans_and_the_backend_name_the_same_kernel(
    interpreted_kernels, monkeypatch, tmp_path, startup_timeline
):
    """One real frame of the family at 16x16 through the worker's queue:
    the program's `render.compile` spans (JAX's own events, whatever it
    built for the frame) carry `args.kernel`, and so does the frame's
    `dispatch` step and no other step."""
    import asyncio

    from tpu_render_cluster.obs import MetricsRegistry, Tracer
    from tpu_render_cluster.obs import startup as startup_module
    from tpu_render_cluster.traces.worker_trace import WorkerTraceBuilder
    from tpu_render_cluster.utils.cancellation import CancellationToken
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend
    from tpu_render_cluster.worker.queue import WorkerAutomaticQueue
    from tests.test_steps import SenderStub

    monkeypatch.setattr(startup_module, "COMPILE_SPAN_FLOOR_SECONDS", 0.0)
    fresh_programs()
    backend = TpuRaytraceBackend(base_directory=tmp_path, width=16, height=16, samples=1, max_bounces=2)
    backend.warm(SCENE)
    compiles = [e["args"] for e in startup_timeline.events() if e["cat"] == "render.compile"]
    named = [args for args in compiles if "kernel" in args]  # what the backend's first call built
    assert {args["kernel"] for args in named} == {"mesh_fused"}
    assert {"program", "jit(program)"} <= {args["fun_name"] for args in named}  # beside what JAX says of it
    span_tracer = Tracer("worker-under-test")

    async def drive():
        queue = WorkerAutomaticQueue(
            backend, SenderStub(), WorkerTraceBuilder(), CancellationToken(),
            metrics=MetricsRegistry(), span_tracer=span_tracer,
        )
        queue.queue_frame(make_job("02_physics-mesh_240f-1w", 8), 3)
        queue.start()
        while queue.queue_size():
            await asyncio.sleep(0.005)
        await queue.join()

    asyncio.run(drive())
    steps = [e for e in span_tracer.events() if e.get("cat") == "worker.step"]
    assert {e["args"].get("kernel") for e in steps if e["name"] == "dispatch"} == {"mesh_fused"}
    assert all("kernel" not in e["args"] for e in steps if e["name"] != "dispatch")
    assert backend.trace_kernels == {f"{SCENE}@16x16x1x2 masked": "mesh_fused"}
    # outside the backend's call nothing names a kernel
    with startup_module.compile_span_args(kernel="outer"), startup_module.compile_span_args(frame=3):
        assert startup_module._span_args.args == {"kernel": "outer", "frame": 3}
    assert startup_module._span_args.args == {}


def test_the_workers_exit_snapshot_names_the_kernels_it_built(tmp_path, monkeypatch):
    import json
    import sys

    from tpu_render_cluster.worker import main as worker_main
    from tpu_render_cluster.worker.backends.mock import MockBackend

    class Named(MockBackend):
        trace_kernels = {"02_physics-mesh@512x512x8x4 masked": "mesh_fused"}

    async def no_job(*_args, **_kwargs):
        return None

    monkeypatch.setattr(worker_main, "make_backend", lambda _args: Named())
    monkeypatch.setattr(worker_main, "initialize_console_and_file_logging", lambda _path: None)
    monkeypatch.setattr(worker_main, "_run_worker", no_job)
    monkeypatch.setattr(sys, "argv", ["worker"])
    assert worker_main.main(["--masterServerHost", "127.0.0.1", "--masterServerPort", "1", "--baseDirectory", str(tmp_path)]) == 0
    (snapshot,) = (tmp_path / "obs").glob("worker-*_metrics.json")
    assert json.loads(snapshot.read_text())["trace_kernels"] == Named.trace_kernels


# -- the pictures are the parent's ----------------------------------------------------------


def parents_trace_paths(
    scene, origins, directions, key, *, max_bounces=4, mesh=None, rng_lanes=None, use_tlas=None, quant=None,
    live_counts=None, walk_counts=None,
):
    """`integrator.trace_paths` of the parent commit (ea10393) on its Pallas
    roads, its chain of `if`s as it stood."""
    import jax.numpy as jnp

    from tpu_render_cluster.render import integrator, pallas_kernels

    assert pallas_kernels.pallas_enabled()
    seed = integrator.trace_seed(key)
    if mesh is None and rng_lanes is None:
        return pallas_kernels.trace_paths_fused(scene, origins, directions, seed, max_bounces=max_bounces)
    if mesh is None:
        return pallas_kernels.trace_paths_fused(
            scene, origins, directions, seed, max_bounces=max_bounces, lane=jnp.asarray(rng_lanes, jnp.int32),
        )
    if rng_lanes is None and pallas_kernels.mesh_megakernel_eligible(mesh):
        return pallas_kernels.trace_paths_fused_mesh(
            scene, mesh, origins, directions, seed, max_bounces=max_bounces, use_tlas=use_tlas, quant=quant,
        )
    return integrator._trace_paths_deep(
        scene, mesh, origins, directions, seed, max_bounces=max_bounces, rng_lanes=rng_lanes, use_tlas=use_tlas,
        quant=quant, live_counts=live_counts, walk_counts=walk_counts,
    )


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("family", sorted(FAMILY_KERNELS))
def test_one_frame_of_each_family_is_byte_for_byte_the_parents_picture(family, interpreted_kernels, monkeypatch):
    import hashlib

    from tpu_render_cluster.render import integrator

    frame = 32 if family == SCENE else 304
    shape = (16, 16, 1, 2) if family == "03_physics-2-scan" else (32, 32, 2, 4)

    def picture() -> str:
        fresh_programs()
        image = np.asarray(integrator.fused_frame_renderer(family, *shape)(frame))
        assert image.std() > 2.0
        return hashlib.sha256(image.tobytes()).hexdigest()

    ours = picture()
    monkeypatch.setattr(integrator, "trace_paths", parents_trace_paths)
    try:
        parents = picture()
    finally:
        monkeypatch.undo()
        fresh_programs()
    assert ours == parents


# -- the configuration's data ---------------------------------------------------------------


@pytest.mark.parametrize("first", range(1, 17))
def test_the_checked_frames_are_32_and_36_whatever_the_first_frame(first):
    config = rule.config_of(CONFIG)
    start, frames = config["frame_range_from"], config["check"]["frames"]
    assert (start["source"], start["first"], start["span"]) == (1, 1, 16)
    assert start["first"] <= first < start["first"] + start["span"]
    assert check.checked_frames(first, config["frames"], frames) == [32, 36]
    assert first + frames["after"] < frames["quantum"] == 32  # so the seed never moves them


# the smallest backlog, the least rate it has to hold, and the rate the builder read in the cell (PERF.md §5, PR 56)
ROW = (225, 4.6, [2.3962, 2.4943])


def test_the_new_configurations_smallest_backlog_holds_the_rate_it_states(monkeypatch):
    monkeypatch.setitem(rule.BACKLOG_CONFIGS, CONFIG, ROW)
    rule.test_the_smallest_backlog_holds_the_rate_the_configuration_states(CONFIG)
    stated = rule.config_of(CONFIG)["holds_frames_per_s"]
    assert stated["value"] == 4.67 and stated["warmup_frames"] == 8
    # ISSUE 56's rule: the job stays 240 frames while the cell reads under 3.5 frames/s
    assert all(rate < 3.5 for rate in ROW[2]) and rule.config_of(CONFIG)["frames"] == 240


def test_a_seeds_first_frame_lies_inside_the_new_configurations_span(tmp_path):
    rule.test_a_seeds_first_frame_lies_inside_the_span(CONFIG, tmp_path)
