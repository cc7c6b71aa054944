"""The farm as a service on one worker: jobs that state their render shape,
a worker that prepares a job when the service announces it, a scheduler
that hands out no frame of a job before the worker has reported it ready.

- the ``[render]`` table through TOML, ``to_dict`` / ``from_dict`` and the
  job-started message; without it, today's bytes;
- two jobs of different family and shape through an in-process
  ``JobManager`` and the ``tpu-raytrace`` backend at 64x64: every file is,
  bit for bit, what ``run-job`` writes for the same job alone, and agrees
  with ``benchmark/reference/plain_tracer.py`` within its family's stated
  tolerance; what the worker says it holds afterwards;
- a preparation made slow by a stub: the other job's frames keep landing
  and no frame of the preparing job is handed out before ``ready``; a frame
  that reaches the backend early waits and builds nothing twice;
- ``benchmark/reference/plain_service.py`` against hand-written trees.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
import tomllib
from pathlib import Path

import numpy as np
import pytest
from PIL import Image

from tpu_render_cluster.harness.local import run_local_job, run_local_multi_job
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy, JobRender
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.sched.models import JobSpec
from tpu_render_cluster.worker.backends.mock import MockBackend

REPO_ROOT = Path(__file__).resolve().parent.parent
JOB_TOML = """
job_name = "04_very-simple_preview"
project_file_path = "%BASE%/p.blend"
render_script_path = "%BASE%/s.py"
frame_range_from = 1
frame_range_to = 4
wait_for_number_of_workers = 1
output_directory_path = "%BASE%/frames/preview"
output_file_name_format = "rendered-######"
output_file_format = "JPEG"

[frame_distribution_strategy]
strategy_type = "naive-fine"
"""


def make_job(name: str, first: int, last: int, render: dict | None, directory: str | None = None) -> BlenderJob:
    return BlenderJob(
        job_name=name, job_description=None, project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py", frame_range_from=first, frame_range_to=last,
        wait_for_number_of_workers=1,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path=f"%BASE%/frames/{directory or name}",
        output_file_name_format="rendered-######", output_file_format="JPEG",
        render=render,
    )


# -- the [render] table ------------------------------------------------------------


@pytest.mark.parametrize("table", [
    {"width": 64, "height": 48, "samples": 2, "max_bounces": 3},
    {"samples": 1},
])
def test_a_render_table_travels_with_the_job(table):
    text = JOB_TOML + "\n[render]\n" + "\n".join(f"{k} = {v}" for k, v in table.items())
    job = BlenderJob.from_dict(tomllib.loads(text))
    assert job.render == JobRender(**table)
    assert job.render.shape((512, 512, 8, 4)) == (
        table.get("width", 512), table.get("height", 512), table["samples"], table.get("max_bounces", 4),
    )
    assert job.to_dict()["render"] == table
    assert BlenderJob.from_dict(json.loads(json.dumps(job.to_dict()))) == job
    for message in (
        pm.MasterJobStartedEvent(trace_id=7, job_id="job-0001", job=job),
        pm.MasterFrameQueueAddRequest(42, job, 3),
    ):
        assert pm.decode_message(pm.encode_message(message)).job.render == JobRender(**table)


def test_without_the_table_a_job_and_its_announcement_are_todays_bytes():
    job = BlenderJob.from_dict(tomllib.loads(JOB_TOML))
    assert job.render is None and "render" not in job.to_dict()
    assert pm.encode_message(pm.MasterJobStartedEvent()) == '{"message_type":"event_job-started","payload":{}}'
    assert pm.encode_message(pm.MasterJobStartedEvent(trace_id=7, job_id="job-0001")) == (
        '{"message_type":"event_job-started","payload":{"trace_id":7,"job_id":"job-0001"}}'
    )
    assert pm.decode_message(pm.encode_message(pm.MasterJobStartedEvent(job_id="j"))).job is None
    # a worker that reports says so; the reference's handshake stays as it was
    assert "prepares_jobs" not in pm.WorkerHandshakeResponse("first-connection", "1.0.0", 7).to_payload()
    said = pm.WorkerHandshakeResponse("first-connection", "1.0.0", 7, prepares_jobs=True)
    assert pm.decode_message(pm.encode_message(said)).prepares_jobs is True
    ready = pm.WorkerJobReadyEvent("04_very-simple_preview", job_id="job-0001")
    assert pm.decode_message(pm.encode_message(ready)) == ready
    assert pm.WorkerJobReadyEvent("a").to_payload() == {"job_name": "a"}


@pytest.mark.parametrize("table, problem", [
    ({"samples": 0}, "positive integer"),
    ({"width": True}, "positive integer"),
    ({"height": "512"}, "positive integer"),
    ({"spp": 8}, "unknown render key"),
    ({}, "states no size"),
    ("512x512", "must be a table"),
])
def test_a_malformed_render_table_is_refused_when_the_job_is_loaded(table, problem):
    with pytest.raises(ValueError, match=problem):
        BlenderJob.from_dict({**tomllib.loads(JOB_TOML), "render": table})


# -- two families, two shapes, one worker ------------------------------------------

SPHERES = {"width": 64, "height": 64, "samples": 2, "max_bounces": 4}
MESH = {"width": 64, "height": 64, "samples": 1, "max_bounces": 4}
FAMILY_JOBS = {
    "04_very-simple": ("04_very-simple_svc-0001", 1, 4, SPHERES),
    "03_physics-2-mesh": ("03_physics-2-mesh_svc-0002", 288, 289, MESH),
}


def backend_at(base: Path, samples: int):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    # the worker's own flags say neither job's shape
    return TpuRaytraceBackend(base_directory=base, width=32, height=32, samples=samples, max_bounces=2)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Both jobs through one JobManager and one tpu-raytrace worker."""
    from tpu_render_cluster import obs
    from tpu_render_cluster.obs.startup import reset_startup

    base = tmp_path_factory.mktemp("served")
    previous, obs._global_registry = obs._global_registry, obs.MetricsRegistry()
    reset_startup()
    jobs = {family: make_job(name, first, last, shape) for family, (name, first, last, shape) in FAMILY_JOBS.items()}
    try:
        _traces, job_ids, manager, workers = run_local_multi_job(
            [JobSpec(job=job) for job in jobs.values()], [backend_at(base, 8)], timeout=280.0,
        )
        yield {
            "base": base, "jobs": jobs, "manager": manager, "job_ids": job_ids,
            "said": what_the_worker_says(obs.get_registry()),
            "timeline": workers[0].span_tracer.events(),
        }
    finally:
        obs._global_registry = previous


@pytest.mark.parametrize("family", list(FAMILY_JOBS))
def test_a_served_jobs_files_are_run_jobs_files_bit_for_bit(served, family, tmp_path):
    job = served["jobs"][family]
    assert served["manager"].job_status(served["job_ids"][list(FAMILY_JOBS).index(family)])["status"] == "finished"
    run_local_job(job, [backend_at(tmp_path, 3)], timeout=280.0)  # the same job alone, through run-job
    names = sorted(p.name for p in (served["base"] / "frames" / job.job_name).iterdir())
    assert names == [f"rendered-{frame:06d}.jpg" for frame in job.frame_indices()]
    for name in names:
        together = (served["base"] / "frames" / job.job_name / name).read_bytes()
        assert together == (tmp_path / "frames" / job.job_name / name).read_bytes(), name
        with Image.open(served["base"] / "frames" / job.job_name / name) as image:
            assert image.size == (64, 64) and image.format == "JPEG"


@pytest.mark.parametrize("family", list(FAMILY_JOBS))
def test_a_served_frame_is_the_plain_tracers_image_within_its_familys_tolerance(served, family):
    from benchmark.lib import check
    from benchmark.reference import plain_tracer
    from tpu_render_cluster.render.camera import scene_camera
    from tpu_render_cluster.render.mesh import scene_mesh_set
    from tpu_render_cluster.render.scene import build_scene

    configuration = {"04_very-simple": "04vs-14400f-1w", "03_physics-2-mesh": "03ph2mesh-480f-1w"}[family]
    rule = json.loads((REPO_ROOT / "benchmark/configs" / configuration / "config.json").read_text())["check"]["independent"]
    job = served["jobs"][family]
    frame, shape = job.frame_range_from, FAMILY_JOBS[family][3]
    mesh_set = scene_mesh_set(family, frame)
    mesh = None
    if mesh_set is not None:
        mesh = {key: np.asarray(getattr(mesh_set.bvh, key)) for key in ("v0", "e1", "e2")}
        mesh.update({key: np.asarray(value) for key, value in mesh_set.instances._asdict().items()})
    replicas = plain_tracer.render_crop_replicas(
        {key: np.asarray(value) for key, value in build_scene(family, frame)._asdict().items()},
        {key: np.asarray(value) for key, value in scene_camera(family, frame)._asdict().items()},
        mesh, width=64, height=64, y0=0, x0=0, size=64, samples=shape["samples"],
        max_bounces=shape["max_bounces"], replicas=rule["replicas"], seed=40,
    )
    served_pixels = check.load_rgb(served["base"] / "frames" / job.job_name / f"rendered-{frame:06d}.jpg")
    ok, excess = check.independent_agreement(
        served_pixels, replicas, block=rule["block"], sigmas=rule["sigmas"], abs_levels=rule["abs_levels"],
    )
    assert ok, excess


def what_the_worker_says(registry) -> dict:
    """The residency series, read as the service's run ended (the run-job
    runs of the tests above feed the same process-wide registry later)."""
    held = registry.gauge("render_resident_geometry_bytes", "", labels=("family", "space"))
    by_family = registry.counter("worker_frames_rendered_by_family_total", "", labels=("family",))
    prepared = registry.histogram("worker_job_prepare_seconds", "", labels=("family",))
    return {
        "programs": registry.gauge("render_resident_program_units", "").value(),
        "held": {family: held.value(family=family, space="vmem") for family in FAMILY_JOBS},
        "vmem": registry.gauge("render_geometry_bytes", "", labels=("space",)).value(space="vmem"),
        "frames": {family: by_family.value(family=family) for family in FAMILY_JOBS},
        "before_ready": registry.counter("worker_frames_before_ready_total", "").value(),
        "switches": registry.counter("worker_program_switches_total", "").value(),
        "prepared": {family: prepared.series(family=family).count for family in FAMILY_JOBS},
    }


def test_the_worker_says_what_it_holds_after_two_families(served):
    said = served["said"]
    assert said["programs"] == 2
    assert said["held"]["03_physics-2-mesh"] > 0 and said["held"]["04_very-simple"] == 0
    assert said["vmem"] == said["held"]["03_physics-2-mesh"]
    assert said["frames"] == {"04_very-simple": 4, "03_physics-2-mesh": 2}
    # no frame met the render thread before its job was resident; the two jobs' frames interleaved
    assert said["before_ready"] == 0 and said["switches"] >= 1
    assert said["prepared"] == {family: 1 for family in FAMILY_JOBS}


def test_each_job_is_one_prepare_span_with_its_three_children(served):
    spans = [e for e in served["timeline"] if e.get("cat") == "worker.prepare"]
    whole = {e["args"]["family"]: e for e in spans if e["name"] == "job_prepare"}
    assert set(whole) == set(FAMILY_JOBS)
    for family, (_, _, _, shape) in FAMILY_JOBS.items():
        assert whole[family]["args"]["resident"] is False
        assert whole[family]["args"]["shape"] == f"64x64x{shape['samples']}x4"
        children = [e for e in spans if e["tid"] == whole[family]["tid"] and e["name"] != "job_prepare"]
        assert [e["name"] for e in children] == ["geometry", "program_build", "first_execute"]
        assert sum(e["dur"] for e in children) == pytest.approx(whole[family]["dur"], rel=0.02, abs=2000)
    phases = served["manager"].metrics.histogram("sched_job_phase_seconds", "", labels=("phase",))
    assert [phases.series(phase=phase).count for phase in ("queued", "admit_to_first_dispatch", "last_result_to_finished")] == [2, 2, 2]


# -- a slow preparation --------------------------------------------------------------


class SlowToPrepare(MockBackend):
    """A mock whose jobs named ``slow*`` take a while to become resident."""

    def __init__(self, seconds: float) -> None:
        super().__init__(render_seconds=0.01)
        self.seconds = seconds
        self.ready_at: dict[str, float] = {}
        self.rendered_at: list[tuple[str, float]] = []

    async def prepare_job(self, job: BlenderJob) -> None:
        if job.job_name.startswith("slow"):
            await asyncio.sleep(self.seconds)
        self.ready_at[job.job_name] = time.time()

    async def render_frame(self, job, frame_index, tile=None):
        self.rendered_at.append((job.job_name, time.time()))
        return await super().render_frame(job, frame_index, tile)


def test_other_jobs_frames_land_while_a_job_is_prepared_and_none_of_its_own_before_ready():
    backend = SlowToPrepare(1.0)
    specs = [JobSpec(job=make_job("slow-shot", 1, 6, None)), JobSpec(job=make_job("quick-preview", 1, 30, None))]
    _traces, job_ids, manager, _workers = run_local_multi_job(specs, [backend], timeout=120.0)
    assert all(manager.job_status(job_id)["status"] == "finished" for job_id in job_ids)
    slow = [at for name, at in backend.rendered_at if name == "slow-shot"]
    quick = [at for name, at in backend.rendered_at if name == "quick-preview"]
    assert len(slow) == 6 and len(quick) == 30
    assert min(slow) >= backend.ready_at["slow-shot"]
    # the preview's frames went on landing under the other job's preparation
    assert sum(1 for at in quick if at < backend.ready_at["slow-shot"]) >= 5
    phases = manager.metrics.histogram("sched_job_phase_seconds", "", labels=("phase",))
    assert phases.series(phase="admit_to_first_dispatch").sum >= 1.0


def test_a_worker_that_never_said_it_prepares_is_ready_for_every_job():
    from tpu_render_cluster.master.worker_handle import WorkerHandle

    quiet = WorkerHandle.__new__(WorkerHandle)  # the C++ daemon's handshake
    assert quiet.is_ready_for("a", "job-0001")
    saying = WorkerHandle.__new__(WorkerHandle)
    saying.prepares_jobs, saying.ready_jobs = True, {("a", "job-0001")}
    assert saying.is_ready_for("a", "job-0001") and not saying.is_ready_for("a", "job-0002")


def test_a_frame_that_arrives_early_waits_for_the_preparation_and_builds_nothing_twice(monkeypatch, tmp_path):
    import jax.numpy as jnp

    from tpu_render_cluster import obs
    from tpu_render_cluster.render import integrator

    monkeypatch.setattr(obs, "_global_registry", obs.MetricsRegistry())
    builds, release = [], threading.Event()

    def slow_factory(*key, **_kwargs):
        builds.append(key)
        release.wait(10)

        def render(_frame):
            return jnp.zeros((8, 8, 3), jnp.uint8), None
        return render

    monkeypatch.setattr(integrator, "fused_frame_renderer", slow_factory)
    backend = backend_at(tmp_path, 1)
    job = make_job("04_very-simple_early", 1, 1, {"width": 8, "height": 8})
    preparing = threading.Thread(target=backend.prepare, args=(job.job_name, backend.program_key(job)))
    preparing.start()
    while not builds:
        time.sleep(0.005)
    rendering = threading.Thread(target=backend._render_sync, args=(job, 1))
    rendering.start()
    time.sleep(0.1)
    assert rendering.is_alive() and len(builds) == 1  # waiting, not building
    release.set()
    preparing.join(10)
    rendering.join(10)
    assert not preparing.is_alive() and not rendering.is_alive()
    assert backend._before_ready.value() == 1
    assert (tmp_path / "frames" / job.job_name / "rendered-000001.jpg").is_file()
    # the frame asked the factory for the resident program, it built none
    assert len(builds) == 2 and builds[0] == builds[1]


# -- start-up's three stages, when a job is prepared after connecting ------------------


def test_a_preparation_under_await_job_is_credited_to_its_stages_and_the_eight_still_add_up():
    from tpu_render_cluster.obs.startup import STARTUP_STAGES, StartupRecorder

    recorder = StartupRecorder(process_start=time.time())
    for stage in ("backend_init", "connect", "await_job"):
        recorder.enter(stage)
    time.sleep(0.05)
    assert recorder.credit("geometry", 0.01) and recorder.credit("program_build", 0.03)
    assert not recorder.credit("await_job", 1.0) and not recorder.credit("first_frame", 1.0)
    recorder.enter("first_frame")
    assert not recorder.credit("first_execute", 1.0)  # another job's, beside frames that land
    began = recorder.process_start
    recorder.finish()
    seconds = recorder.seconds()
    assert seconds["geometry"] == pytest.approx(0.01) and seconds["program_build"] == pytest.approx(0.03)
    assert 0.0 < seconds["await_job"] < 0.05
    assert sum(seconds[stage] for stage in STARTUP_STAGES) == pytest.approx(time.time() - began, abs=0.01)


# -- the plain reference of the service's semantics -------------------------------------


def service_tree(root: Path, spoil: str | None) -> tuple[list[dict], set[str]]:
    jobs = [
        {"name": "a_svc-0001", "directory": "a_svc-0001", "first": 3, "last": 5,
         "name_format": "rendered-######", "file_format": "JPEG", "width": 16, "height": 8},
        {"name": "b_svc-0002", "directory": "b_svc-0002", "first": 1, "last": 2,
         "name_format": "f###", "file_format": "PNG", "width": 8, "height": 8},
        {"name": "a_svc-0003", "directory": "a_svc-0003", "first": 6, "last": 8,
         "name_format": "rendered-######", "file_format": "JPEG", "width": 16, "height": 8},
    ]

    def write(directory: str, name: str, width: int, height: int, file_format: str) -> None:
        (root / directory).mkdir(parents=True, exist_ok=True)
        Image.new("RGB", (width, height), (40, 80, 120)).save(root / directory / name, file_format)

    for frame in (3, 4, 5):
        if not (spoil == "missing" and frame == 4):
            wide = 8 if (spoil == "shape" and frame == 5) else 16
            write("a_svc-0001", f"rendered-{frame:06d}.jpg", wide, 8, "JPEG")
    for frame in (1, 2):
        write("b_svc-0002", f"f{frame:03d}.png", 8, 8, "PNG")
    write("a_svc-0003", "rendered-000006.jpg", 16, 8, "JPEG")  # in flight: one of its three so far
    if spoil == "stray":
        write("a_svc-0001", "rendered-000006.jpg", 16, 8, "JPEG")  # job 3's frame in job 1's directory
    if spoil == "format":
        write("b_svc-0002", "f001.png", 8, 8, "JPEG")
    return jobs, {"a_svc-0001", "b_svc-0002"}


@pytest.mark.parametrize("spoil, problem", [
    (None, None),
    ("missing", "a_svc-0001/rendered-000004.jpg: its job was reported finished and the file is missing"),
    ("shape", "a_svc-0001/rendered-000005.jpg: is (8, 8, 'JPEG'), its job states (16, 8, 'JPEG')"),
    ("stray", "a_svc-0001/rendered-000006.jpg: no submitted job's range holds this file"),
    ("format", "b_svc-0002/f001.png: is (8, 8, 'JPEG'), its job states (8, 8, 'PNG')"),
])
def test_the_plain_service_holds_the_tree_to_the_jobs_that_were_reported_finished(tmp_path, spoil, problem):
    from benchmark.reference import plain_service

    jobs, finished = service_tree(tmp_path, spoil)
    must, may = plain_service.expected(jobs, finished)
    assert len(must) == 5 and len(may) == 3
    assert plain_service.compare(tmp_path, must, may) == ([] if problem is None else [problem])
