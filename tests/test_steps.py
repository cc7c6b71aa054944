"""The steps of a frame (obs.step) and the render loop's states.

The helper alone (exclusivity, nothing kept outside a frame, no JAX where
there was none, a `trc:<step>` annotation in a profile), the three unit
shapes of the tpu-raytrace backend on a 64x64 CPU frame (all six steps,
adding up to the phases), `write_image`'s split (the file on disk is
the parent's, byte for byte), and the worker queue (steps enter the
registry and the timeline with the phases; the loop counter's four states
add up to the loop's wall time; tests/test_frame_pipeline.py holds the same
sum under overlap).
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.obs import (
    FRAME_STEPS,
    MetricsRegistry,
    Tracer,
    frame_steps,
    step,
)
from tpu_render_cluster.obs import tracer as tracer_module
from tpu_render_cluster.traces.worker_trace import FrameRenderTime, WorkerTraceBuilder
from tpu_render_cluster.utils.cancellation import CancellationToken
from tpu_render_cluster.worker.backends.mock import MockBackend
from tpu_render_cluster.worker.queue import LOOP_STATES, WorkerAutomaticQueue

REPO_ROOT = Path(__file__).resolve().parent.parent


def make_job(name: str, frames: int, output: str = "%BASE%/out", file_format: str = "JPEG") -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description=None,
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=1,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path=output,
        output_file_name_format="rendered-#####",
        output_file_format=file_format,
    )


def seconds_by_step(steps) -> dict[str, float]:
    totals: dict[str, float] = {}
    for name, _start, seconds, _cpu in steps:
        totals[name] = totals.get(name, 0.0) + seconds
    return totals


# -- the helper ----------------------------------------------------------------


def test_the_vocabulary_is_six_fixed_names():
    assert FRAME_STEPS == (
        "resolve", "dispatch", "device_wait", "readback", "encode", "file_write"
    )


def test_an_unknown_step_is_refused():
    with pytest.raises(ValueError, match="unknown frame step"):
        with step("compositing"):
            pass


def test_an_inner_step_suspends_the_outer_one():
    with frame_steps() as steps:
        with step("dispatch"):
            time.sleep(0.02)
            with step("device_wait"):
                time.sleep(0.05)
            time.sleep(0.01)
    assert [name for name, _, _, _ in steps] == ["dispatch", "device_wait", "dispatch"]
    totals = seconds_by_step(steps)
    # the inner step's 50 ms are not in the outer's 30
    assert 0.03 <= totals["dispatch"] < 0.045
    assert 0.05 <= totals["device_wait"] < 0.065


def test_a_frames_steps_add_up_to_its_wall_time():
    start = time.perf_counter()
    with frame_steps() as steps:
        with step("resolve"):
            time.sleep(0.003)
        with step("dispatch"):
            for _ in range(4):
                time.sleep(0.002)
                with step("device_wait"):
                    time.sleep(0.004)
        with step("device_wait"):
            time.sleep(0.002)
        with step("readback"):
            time.sleep(0.001)
        with step("encode"):
            time.sleep(0.003)
        with step("file_write"):
            time.sleep(0.002)
    wall = time.perf_counter() - start
    assert abs(sum(seconds for _, _, seconds, _ in steps) - wall) < 0.001
    assert sum(1 for name, _, _, _ in steps if name == "device_wait") == 5


def test_segments_do_not_overlap_and_are_in_the_order_they_ended():
    with frame_steps() as steps:
        with step("dispatch"):
            with step("device_wait"):
                time.sleep(0.002)
            with step("readback"):
                time.sleep(0.002)
    ends = [start + seconds for _, start, seconds, _ in steps]
    assert ends == sorted(ends)
    for (_, next_start, _, _), end in zip(steps[1:], ends):
        assert next_start >= end - 1e-4  # wall clock against the monotonic one


# -- the CPU clock beside the wall clock ----------------------------------------


def spin_cpu(seconds: float) -> None:
    """Work until THIS thread's CPU clock has run `seconds`: however loaded
    the machine, the thread has then used that much CPU and no less."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_a_step_that_computes_has_its_cpu_seconds_within_its_wall_and_over_half_of_it():
    # At least 30 ms of CPU by construction, and never more than the wall (the CPU clock is
    # read inside the wall clock's reads; the two tick apart by far less than a millisecond).
    # Over half of the wall on a machine that gives the thread a core: three goes, because
    # a shared machine may take the core away for longer than the step once.
    shares = []
    for _ in range(3):
        with frame_steps() as steps:
            with step("encode"):
                spin_cpu(0.03)
        ((name, _start, seconds, cpu_seconds),) = steps
        assert name == "encode" and 0.03 <= cpu_seconds <= seconds + 1e-3
        shares.append(cpu_seconds / seconds)
        if shares[-1] > 0.5:
            break
    assert max(shares) > 0.5, shares


def test_a_step_that_sleeps_has_hardly_any_cpu_seconds():
    with frame_steps() as steps:
        with step("device_wait"):
            time.sleep(0.03)
    ((_, _, seconds, cpu_seconds),) = steps
    assert seconds >= 0.03 and 0.0 <= cpu_seconds < 0.005


def test_nested_steps_keep_their_cpu_seconds_apart_as_they_keep_their_wall_seconds():
    before = time.thread_time()
    with frame_steps() as steps:
        with step("encode"):
            spin_cpu(0.02)
            with step("device_wait"):
                time.sleep(0.03)
            spin_cpu(0.01)
    used = time.thread_time() - before
    assert [name for name, _, _, _ in steps] == ["encode", "device_wait", "encode"]
    (_, _, _, first), (_, _, waited, waiting), (_, _, _, last) = steps
    # the outer step's CPU is not the inner's, and the other way round
    assert first >= 0.02 and last >= 0.01 and waiting < 0.005 and waited >= 0.03
    # exclusive: the three add up to no more than the thread used from end to end
    assert first + waiting + last <= used
    for _, _, seconds, cpu_seconds in steps:
        assert cpu_seconds <= seconds + 1e-3


def test_the_cpu_clock_is_read_for_the_three_steps_a_metric_reads_and_for_no_other():
    """A read of the thread's CPU clock is a call into the sentry on the
    chip's host (6 us where the wall clock's is 0.09; PERF.md §5): the
    steps nobody reads a CPU metric of carry None, and cost no read."""
    from tpu_render_cluster.obs import CPU_TIMED_STEPS

    assert CPU_TIMED_STEPS == ("device_wait", "encode", "file_write")
    reads = []
    real = time.thread_time
    with frame_steps() as steps:
        tracer_module.time.thread_time = lambda: reads.append(1) or real()
        try:
            for name in FRAME_STEPS:
                with step(name):
                    pass
        finally:
            tracer_module.time.thread_time = real
    assert len(reads) == 2 * len(CPU_TIMED_STEPS)  # a segment's open and its close
    for name, _start, seconds, cpu_seconds in steps:
        if name in CPU_TIMED_STEPS:
            assert 0.0 <= cpu_seconds <= seconds + 1e-3
        else:
            assert cpu_seconds is None


def test_only_the_steps_own_thread_counts():
    """Another thread at work while a step sleeps is not the step's CPU:
    what several save threads use beside each other stays each one's own."""
    busy = threading.Thread(target=spin_cpu, args=(0.03,))
    with frame_steps() as steps:
        with step("file_write"):
            busy.start()
            busy.join(timeout=30)
    assert not busy.is_alive()
    ((_, _, seconds, cpu_seconds),) = steps
    assert seconds >= 0.03 and cpu_seconds < 0.01


def test_a_cpu_clock_that_steps_back_gives_a_step_no_cpu_seconds_and_not_fewer():
    """The queue feeds a counter with a step's CPU seconds, and a counter
    refuses a negative amount: one backward step of a host's thread clock
    would end the worker's loop."""
    readings = iter([5.0, 4.99])
    real = time.thread_time
    with frame_steps() as steps:
        tracer_module.time.thread_time = lambda: next(readings)
        try:
            with step("encode"):
                pass
        finally:
            tracer_module.time.thread_time = real
    ((_, _, _, cpu_seconds),) = steps
    assert cpu_seconds == 0.0


def test_nothing_is_kept_outside_a_frame():
    with step("dispatch"):  # render.cli, warm(): no frame in hand
        pass
    with frame_steps() as steps:
        pass
    assert steps == []


def test_a_frames_steps_are_the_threads_own():
    seen = {}

    def other():
        with frame_steps() as steps:
            with step("encode"):
                time.sleep(0.002)
        seen["other"] = steps

    with frame_steps() as mine:
        thread = threading.Thread(target=other)
        thread.start()
        with step("dispatch"):
            time.sleep(0.004)
        thread.join()
    assert [name for name, _, _, _ in mine] == ["dispatch"]
    assert [name for name, _, _, _ in seen["other"]] == ["encode"]


def test_a_failed_step_is_closed_and_the_outer_one_resumes():
    with frame_steps() as steps:
        with step("dispatch"):
            with pytest.raises(RuntimeError):
                with step("device_wait"):
                    raise RuntimeError("device lost")
            time.sleep(0.001)
    assert [name for name, _, _, _ in steps] == ["dispatch", "device_wait", "dispatch"]
    assert not tracer_module._steps_local.stack


def test_a_step_imports_no_jax_where_there_was_none():
    code = (
        "import sys\n"
        "from tpu_render_cluster.obs import frame_steps, step\n"
        "with frame_steps() as steps:\n"
        "    with step('encode'):\n"
        "        with step('file_write'):\n"
        "            pass\n"
        "assert len(steps) == 3, steps\n"
        "assert 'jax' not in sys.modules\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_master_still_imports_no_jax():
    """Importing the master (and the worker runtime's queue, which feeds the
    step series) pulls in the step helper and must not pull in JAX."""
    code = (
        "import sys\n"
        "import tpu_render_cluster.master.main\n"
        "import tpu_render_cluster.master.assembly\n"
        "import tpu_render_cluster.worker.queue\n"
        "from tpu_render_cluster.obs import step\n"
        "with step('file_write'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'the master imported JAX'\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]


def test_a_profile_carries_the_steps_on_its_own_clock(tmp_path):
    """A jax.profiler trace taken on the CPU holds a `trc:<step>` host
    event for every step opened while it ran."""
    import jax

    jax.numpy.zeros(1).block_until_ready()  # backend start-up stays out of the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with step("dispatch"):
            with step("device_wait"):
                time.sleep(0.002)
        with step("file_write"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    found: dict[str, list[float]] = {}
    for plane in data.planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("trc:"):
                    found.setdefault(event.name, []).append(event.duration_ns / 1e9)
    assert set(found) == {"trc:dispatch", "trc:device_wait", "trc:file_write"}
    assert len(found["trc:dispatch"]) == 2  # suspended, then resumed
    assert 0.002 <= max(found["trc:device_wait"]) < 0.05


# -- the backend's three unit shapes ----------------------------------------------


@pytest.fixture
def interpreted_kernels(monkeypatch):
    import jax

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    yield
    jax.clear_caches()


# What the backend is given, by the tier that must render it: a whole frame
# on one device, a tile of a 2x2 grid, a whole frame across the local mesh.
TIERS = {
    "masked": {"sharding": None, "tile": None},
    "region": {"sharding": None, "tile": 3},
    "sharded": {"sharding": "tile", "tile": None},
}
BOUNCES = 3


def render_one(tier: str, tmp_path: Path):
    import dataclasses

    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    job = make_job("04_very-simple_steps", 4)
    tile = TIERS[tier]["tile"]
    if tile is not None:
        job = dataclasses.replace(job, tile_grid=(2, 2))
    backend = TpuRaytraceBackend(
        base_directory=tmp_path, width=64, height=64, samples=2,
        max_bounces=BOUNCES, sharding=TIERS[tier]["sharding"],
    )
    backend._render_sync(job, 1, tile)  # compiles; the frame below is the measured one
    return backend, job, backend._render_sync(job, 1, tile)


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_every_tier_names_all_six_steps_and_they_add_up_to_the_phases(
    tier, tmp_path, interpreted_kernels
):
    _backend, _job, timing = render_one(tier, tmp_path)
    totals = seconds_by_step(timing.steps)
    assert set(totals) == set(FRAME_STEPS)
    read_render = (
        timing.finished_loading_at - timing.started_process_at
        + timing.finished_rendering_at - timing.started_rendering_at
    )
    write = timing.file_saving_finished_at - timing.file_saving_started_at
    in_render = sum(totals[name] for name in ("resolve", "dispatch", "device_wait", "readback"))
    in_write = totals["encode"] + totals["file_write"]
    # Within 2%, or within half a millisecond where the phase is so short
    # (a 64x64 file is written in under a millisecond) that entering and
    # leaving three context managers on a loaded machine is more than 2%.
    assert in_render == pytest.approx(read_render, rel=0.02, abs=5e-4)
    assert in_write == pytest.approx(write, rel=0.02, abs=5e-4)
    assert in_render <= read_render and in_write <= write  # steps lie inside the phases
    # and to the frame: what has no step is the bookkeeping after the file
    frame = timing.exited_process_at - timing.started_process_at
    assert frame - (in_render + in_write) < 0.002
    ends = [start + seconds for _, start, seconds, _ in timing.steps]
    assert ends == sorted(ends)
    # a whole frame in the job's format; a tile always as PNG, for the master to stitch
    written = sorted(path.name for path in (tmp_path / "out").iterdir())
    assert len(written) == 1
    assert written[0].endswith(".png" if tier == "region" else ".jpg")


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_every_tier_waits_for_the_device_once(tier, tmp_path, interpreted_kernels):
    """One program a frame, whatever the unit: the render thread blocks on
    the device once (host_syncs_per_frame 1.0), then copies, then writes."""
    _backend, _job, timing = render_one(tier, tmp_path)
    names = [name for name, _, _, _ in timing.steps]
    assert names == list(FRAME_STEPS[:4]) + ["file_write", "encode", "file_write"]


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_every_frame_counts_once_under_the_tier_that_rendered_it(
    tier, tmp_path, interpreted_kernels
):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    counter = TpuRaytraceBackend._tier_frames_counter()

    def read() -> dict[str, float]:
        return {name: counter.value(tier=name) for name in TIERS}

    before = read()
    render_one(tier, tmp_path)  # renders its unit twice
    after = read()
    assert {name: after[name] - before[name] for name in TIERS} == {
        name: (2 if name == tier else 0) for name in TIERS
    }


@pytest.mark.parametrize("tier", ["masked", "region"])
def test_a_program_is_counted_when_it_is_built_and_not_per_frame(
    tier, tmp_path, interpreted_kernels
):
    """render_compiles_total rises on the renderer factory's cache miss: once
    for a shape it has not seen, never again for that shape's later frames,
    once more for another shape."""
    import dataclasses

    from tpu_render_cluster.obs import render_compile_counter
    from tpu_render_cluster.render.integrator import (
        fused_frame_renderer,
        fused_region_renderer,
    )
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    fused_frame_renderer.cache_clear()
    fused_region_renderer.cache_clear()
    counter = render_compile_counter()
    tile = TIERS[tier]["tile"]
    job = make_job("04_very-simple_steps", 4)
    if tile is not None:
        job = dataclasses.replace(job, tile_grid=(2, 2))

    def frames(width: int) -> float:
        backend = TpuRaytraceBackend(
            base_directory=tmp_path, width=width, height=32, samples=1, max_bounces=2,
        )
        before = counter.value()
        for frame in (1, 2, 3):
            backend._render_sync(job, frame, tile)
        return counter.value() - before

    assert frames(32) == 1  # three frames, one program
    assert frames(32) == 0  # a second backend of the same shape: the same program
    assert frames(48) == 1  # another shape, another program


@pytest.mark.parametrize("scene,launches", [("03_physics-2-mesh", BOUNCES), ("04_very-simple", 0)])
def test_the_one_program_tier_reports_the_occupancy_of_its_bounce_launches(
    scene, launches, tmp_path, interpreted_kernels
):
    """A deep mesh frame is one launch per bounce, each at the width the
    program picked from its live count: live counts and widths come back
    with the image (still one sync) and feed the three launch series —
    launched lanes are the sum of the widths, so live / launched lies above
    what the frame's full width would give. A
    scene whose program launches no per-bounce kernel feeds nothing."""
    from tpu_render_cluster.render.integrator import fused_frame_renderer
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    def read() -> tuple[float, float, float, float]:
        occupancy = TpuRaytraceBackend._launch_occupancy_histogram().series()
        return (
            occupancy.count if occupancy else 0, occupancy.sum if occupancy else 0.0,
            TpuRaytraceBackend._launched_lanes_counter().value(),
            TpuRaytraceBackend._live_lanes_counter().value(),
        )

    backend = TpuRaytraceBackend(
        base_directory=tmp_path, width=32, height=32, samples=2,
        max_bounces=BOUNCES,
    )
    job = make_job(f"{scene}_steps", 4)
    before = read()
    timing = backend._render_sync(job, 1)
    count, total, launched, live = (b - a for a, b in zip(before, read()))
    rays = 32 * 32 * 2
    assert [name for name, _, _, _ in timing.steps].count("device_wait") == 1
    assert count == launches
    if launches:
        # The backend's own (cached) program, asked again for the same frame.
        _image, reported = fused_frame_renderer(scene, 32, 32, 2, BOUNCES, with_live=True)(1)
        reported = np.asarray(reported)
        assert launched == reported[:, 1].sum() and live == reported[:, 0].sum()
        assert total == pytest.approx((reported[:, 0] / reported[:, 1]).sum())
        # all live at first, then rays die; a bounce ran narrow, so live / launched
        # lies above what launches of the frame's full width would give
        assert rays <= live < launched < rays * launches
    else:
        assert (launched, live, total) == (0, 0, 0)


def test_the_live_counts_ride_the_same_image(interpreted_kernels):
    from tpu_render_cluster.render.integrator import fused_frame_renderer, launch_width_ladder

    plain = np.asarray(fused_frame_renderer("03_physics-2-mesh", 32, 32, 2, BOUNCES)(3))
    image, launches = fused_frame_renderer("03_physics-2-mesh", 32, 32, 2, BOUNCES, with_live=True)(3)
    live, widths = np.asarray(launches).T
    assert np.array_equal(np.asarray(image), plain)
    assert live.shape == (BOUNCES,) and live[0] == widths[0] == 32 * 32 * 2
    assert (np.diff(live) <= 0).all() and live[-1] > 0
    # every launch at a rung that holds its live rays, never wider than the last
    assert set(widths) <= set(launch_width_ladder(32 * 32 * 2))
    assert (live <= widths).all() and (np.diff(widths) <= 0).all() and widths[-1] < widths[0]
    image, launches = fused_frame_renderer("04_very-simple", 32, 32, 2, BOUNCES, with_live=True)(3)
    assert launches is None and image.shape == (32, 32, 3)


def test_the_tier_counter_is_exposed_at_zero_before_any_frame(monkeypatch):
    from tpu_render_cluster import obs
    from tpu_render_cluster.obs.prometheus import lint_metric, render_prometheus
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    monkeypatch.setattr(obs, "_global_registry", MetricsRegistry())
    TpuRaytraceBackend(width=8, height=8, samples=1, max_bounces=2)
    text = render_prometheus(obs.get_registry().snapshot())  # refuses a name that fails the lint
    for tier in ("masked", "region", "sharded"):
        assert f'render_tier_frames_total{{tier="{tier}"}} 0' in text
    assert lint_metric("render_tier_frames_total", "counter", ("tier",)) == []


def test_the_steps_stay_off_the_wire_and_out_of_the_raw_trace(tmp_path, interpreted_kernels):
    _backend, _job, timing = render_one("masked", tmp_path)
    assert timing.steps
    assert set(timing.to_dict()) == {
        "started_process_at", "finished_loading_at", "started_rendering_at",
        "finished_rendering_at", "file_saving_started_at", "file_saving_finished_at",
        "exited_process_at",
    }
    assert FrameRenderTime.from_dict(timing.to_dict()) == timing  # steps do not compare
    assert FrameRenderTime.from_dict(timing.to_dict()).steps == ()


# -- write_image -----------------------------------------------------------------


def parents_write_image(path: Path, pixels: np.ndarray, image_format: str) -> None:
    """`write_image` as the parent commit had it: encoded straight into the
    temporary file."""
    from PIL import Image

    path.parent.mkdir(parents=True, exist_ok=True)
    image = Image.fromarray(np.asarray(pixels))
    fd, tmp_name = tempfile.mkstemp(prefix=f".{path.name}.", suffix=".tmp", dir=path.parent)
    with os.fdopen(fd, "wb") as f:
        if image_format == "JPEG":
            image.save(f, image_format, quality=90)
        else:
            image.save(f, image_format)
    os.replace(tmp_name, path)


@pytest.mark.parametrize("file_format,image_format,extension", [
    ("JPEG", "JPEG", ".jpg"), ("JPG", "JPEG", ".jpg"), ("PNG", "PNG", ".png"), ("EXR", "PNG", ".png"),
])
def test_write_image_writes_the_parents_bytes(tmp_path, file_format, image_format, extension):
    from tpu_render_cluster.render.image_io import write_image

    rng = np.random.default_rng(7)
    gradient = np.linspace(0, 255, 512 * 512 * 3).reshape(512, 512, 3)
    pixels = np.clip(gradient + rng.normal(0, 20, gradient.shape), 0, 255).astype(np.uint8)
    ours, theirs = tmp_path / "ours" / f"f{extension}", tmp_path / "theirs" / f"f{extension}"
    with frame_steps() as steps:
        write_image(ours, pixels, file_format)
    parents_write_image(theirs, pixels, image_format)
    assert ours.read_bytes() == theirs.read_bytes()
    assert [name for name, _, _, _ in steps] == ["encode", "file_write"]
    assert [p.name for p in ours.parent.iterdir()] == [ours.name]  # no temporary file left


def test_write_image_leaves_no_temporary_file_when_the_write_fails(tmp_path, monkeypatch):
    from tpu_render_cluster.render import image_io

    def refuse(*_args, **_kwargs):
        raise OSError("disk full")

    monkeypatch.setattr(os, "replace", refuse)
    with pytest.raises(OSError, match="disk full"):
        image_io.write_image(tmp_path / "f.png", np.zeros((8, 8, 3), np.uint8), "PNG")
    assert list(tmp_path.iterdir()) == []


# -- the worker queue ------------------------------------------------------------


class SenderStub:
    def __init__(self, seconds: float = 0.0) -> None:
        self.sent = []
        self.seconds = seconds

    async def send_message(self, message) -> None:
        if self.seconds:
            await asyncio.sleep(self.seconds)
        self.sent.append(message)


class SteppedMockBackend(MockBackend):
    """A mock frame that also names its steps, as the tpu-raytrace backend does."""

    async def render_frame(self, job, frame_index, tile=None):
        import dataclasses

        timing = await super().render_frame(job, frame_index, tile)
        at = timing.started_process_at
        steps = []
        for name, seconds in (
            ("resolve", 0.001), ("dispatch", 0.002), ("device_wait", 0.004), ("dispatch", 0.001),
            ("device_wait", 0.003), ("readback", 0.001), ("encode", 0.002), ("file_write", 0.001),
        ):
            steps.append((name, at, seconds, seconds / 4))
            at += seconds
        return dataclasses.replace(timing, steps=tuple(steps))


def loop_clock(queue: WorkerAutomaticQueue) -> list[float]:
    """The loop's own clock: the instants (`time.perf_counter`, as the loop
    read them) at which it moved from one state to the next. The first is
    where it entered its first state, the last where `join()` let go of the
    last one, and what lies between two of them was charged to one state."""
    edges: list[float] = []
    enter = queue._enter_loop_state

    def watched(state):
        enter(state)
        edges.append(queue._loop_state_since)

    queue._enter_loop_state = watched
    return edges


def run_queue(backend, frames: int, *, idle_seconds: float = 0.0, sender_seconds: float = 0.0):
    """Returns the loop's wall time by its own clock, the registry and the tracer."""
    metrics, span_tracer = MetricsRegistry(), Tracer("worker-test")

    async def drive():
        queue = WorkerAutomaticQueue(
            backend, SenderStub(sender_seconds), WorkerTraceBuilder(), CancellationToken(),
            metrics=metrics, span_tracer=span_tracer,
        )
        edges = loop_clock(queue)
        job = make_job("steps-mock", frames)

        async def starve() -> None:
            # the sleep begins once the loop IS starving, so all of it lies in that state
            while idle_seconds and queue._loop_state != "no_work":
                await asyncio.sleep(0.001)
            await asyncio.sleep(idle_seconds)

        queue.start()
        await starve()  # nothing queued yet
        for frame in range(1, frames + 1):
            queue.queue_frame(job, frame)
        while len(backend.rendered_frames) < frames or queue.queue_size():
            await asyncio.sleep(0.005)
        await starve()
        await queue.join()
        return edges[-1] - edges[0]

    return asyncio.run(drive()), metrics, span_tracer


def test_the_loops_four_states_add_up_to_its_wall_time():
    backend = MockBackend(load_seconds=0.002, render_seconds=0.02, save_seconds=0.002)
    wall, metrics, _ = run_queue(backend, 6, idle_seconds=0.25, sender_seconds=0.003)
    counter = metrics.counter("worker_loop_seconds_total", labels=("state",))
    by_state = {state: counter.value(state=state) for state in LOOP_STATES}
    # The partition: every instant between the loop's first state and its last is charged
    # to exactly one of the four, so they add up to the loop's own clock (to rounding: the
    # counter adds as many floats as the loop made turns). Not to a clock the test reads
    # round the loop: a shared machine puts milliseconds between the two.
    assert sum(by_state.values()) == pytest.approx(wall, abs=1e-6)
    # Each state from the side a sleep guarantees: a sleep never ends early, so a state
    # holds at least the sleeps that lie in it, and, the four adding up to the wall, at
    # most the wall less the others' sleeps.
    assert by_state["render_call"] >= 6 * 0.024  # six frames of three sleeps each
    assert by_state["no_work"] >= 2 * 0.25  # starved before the frames and after them
    assert by_state["report"] >= 6 * 2 * 0.003  # two events a frame through the sender
    assert by_state["save_wait"] == 0.0  # a backend with no save stage never fills the pipeline


def test_a_failed_frame_is_charged_to_the_render_call_and_the_loop_goes_on():
    backend = MockBackend(load_seconds=0.001, render_seconds=0.005, save_seconds=0.001, fail_frames={2})

    async def drive():
        metrics = MetricsRegistry()
        queue = WorkerAutomaticQueue(
            backend, SenderStub(), WorkerTraceBuilder(), CancellationToken(), metrics=metrics,
        )
        job = make_job("steps-mock-fail", 3)
        queue.start()
        for frame in (1, 2, 3):
            queue.queue_frame(job, frame)
        while queue.queue_size():
            await asyncio.sleep(0.005)
        await queue.join()
        return metrics

    metrics = asyncio.run(drive())
    counter = metrics.counter("worker_loop_seconds_total", labels=("state",))
    assert backend.rendered_frames == [1, 3]
    assert counter.value(state="render_call") > 0.01 and counter.value(state="report") > 0


def test_draining_time_is_nobodys():
    async def drive():
        metrics = MetricsRegistry()
        queue = WorkerAutomaticQueue(
            MockBackend(), SenderStub(), WorkerTraceBuilder(), CancellationToken(), metrics=metrics,
        )
        queue.start()
        await asyncio.sleep(0.05)
        await queue.drain()
        while queue._loop_state is not None:  # the drain woke the loop; it parks on its next turn
            await asyncio.sleep(0.001)
        starved = metrics.counter("worker_loop_seconds_total", labels=("state",)).value(state="no_work")
        await asyncio.sleep(0.3)
        await queue.join()
        return starved, metrics.counter("worker_loop_seconds_total", labels=("state",)).value(state="no_work")

    at_drain, at_end = asyncio.run(drive())
    assert at_drain >= 0.05  # what it starved before the drain stays charged
    assert at_end == at_drain  # and nothing after it, however long the worker lingers


def test_steps_enter_the_registry_and_the_timeline_with_the_phases():
    _, metrics, span_tracer = run_queue(SteppedMockBackend(render_seconds=0.005), 3)
    histogram = metrics.histogram("worker_frame_step_seconds", labels=("step",))
    phases = metrics.histogram("worker_frame_phase_seconds", labels=("phase",))
    assert histogram.buckets == phases.buckets
    snapshot = metrics.snapshot()["worker_frame_step_seconds"]["series"]
    by_step = {key.removeprefix("step="): entry for key, entry in snapshot.items()}
    assert set(by_step) == set(FRAME_STEPS)
    assert by_step["device_wait"]["count"] == 6  # twice a frame: the host syncs
    assert by_step["device_wait"]["sum"] == pytest.approx(3 * 0.007)
    assert by_step["dispatch"]["sum"] == pytest.approx(3 * 0.003)
    events = span_tracer.events()
    steps = [e for e in events if e.get("cat") == "worker.step"]
    assert len(steps) == 3 * 8 and {e["name"] for e in steps} == set(FRAME_STEPS)
    assert {e["args"]["frame"] for e in steps} == {1, 2, 3}
    phase_events = [e for e in events if e.get("cat") == "worker"]
    assert {e["tid"] for e in steps}.isdisjoint({e["tid"] for e in phase_events})
    names = {m["args"]["name"] for m in span_tracer.metadata_events() if m["name"] == "thread_name"}
    assert {"frames", "steps"} <= names


def test_the_timeline_with_steps_passes_the_trace_validator(tmp_path):
    from tpu_render_cluster.obs import validate_trace_file

    _, _, span_tracer = run_queue(SteppedMockBackend(render_seconds=0.005), 4)
    path = span_tracer.export(tmp_path / "worker-test_trace-events.json")
    assert validate_trace_file(path) == []


def test_a_backend_without_steps_feeds_the_phases_alone():
    _, metrics, span_tracer = run_queue(MockBackend(render_seconds=0.005), 2)
    assert "worker_frame_step_seconds" not in {
        name for name, family in metrics.snapshot().items() if family["series"]
    }
    assert not [e for e in span_tracer.events() if e.get("cat") == "worker.step"]
    assert metrics.counter("worker_frames_rendered_total").value() == 2


def test_the_event_buffer_holds_the_sources_largest_job_on_one_worker():
    """14,400 frames, each with four phase spans, four flow steps and a
    frame's seven step segments (`file_write` twice, round `encode`), fit
    under the default cap."""
    per_frame = 4 + 4 + 7
    assert tracer_module.MAX_EVENTS >= 14_400 * per_frame
    assert Tracer("worker-full")._max_events == tracer_module.MAX_EVENTS


# -- which timeline is which chip's ---------------------------------------------


@pytest.mark.parametrize("visible,chip", [(None, None), ("2", 2), ("0,1", None)])
def test_the_device_stamp_carries_the_chips_index(monkeypatch, visible, chip):
    from tpu_render_cluster.utils.accelerator import chip_environment, require_tpu_device

    if visible is None:
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    else:
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", visible)
    stamp = require_tpu_device()
    assert stamp["chip"] == chip
    assert stamp["device_id"] == 0 and stamp["platform"] == "cpu"
    assert chip_environment(2)["TPU_VISIBLE_CHIPS"] == "2"  # what a launcher sets is what is read


def test_process_labels_ride_the_timelines_metadata(tmp_path):
    from tpu_render_cluster.obs import validate_trace_file

    tracer = Tracer("worker-abc")
    assert [m["name"] for m in tracer.metadata_events()] == ["process_name"]
    tracer.process_labels = {"platform": "tpu", "device_id": 0, "chip": 3}
    tracer.complete("render", cat="worker", start_wall=1.0, duration=0.5, track="frames")
    (labels,) = [m for m in tracer.metadata_events() if m["name"] == "process_labels"]
    assert labels["ph"] == "M" and labels["pid"] == tracer.pid
    assert labels["args"] == {
        "labels": "platform=tpu, device_id=0, chip=3", "platform": "tpu", "device_id": 0, "chip": 3,
    }
    assert validate_trace_file(tracer.export(tmp_path / "t.json")) == []
