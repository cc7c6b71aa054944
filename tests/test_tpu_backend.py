"""tpu-raytrace worker backend + graft entry tests (CPU mesh)."""

import asyncio

import numpy as np
import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.worker.backends import create_backend


def make_job(tmp_path, scene_job_name="04_very-simple_demo") -> BlenderJob:
    return BlenderJob(
        job_name=scene_job_name,
        job_description=None,
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=4,
        wait_for_number_of_workers=1,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/frames",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )


def test_tpu_raytrace_backend_renders_and_traces(tmp_path):
    backend = create_backend(
        "tpu-raytrace",
        base_directory=tmp_path,
        width=32,
        height=32,
        samples=1,
        max_bounces=2,
    )
    job = make_job(tmp_path)
    timing = asyncio.run(backend.render_frame(job, 3))

    output = tmp_path / "frames" / "rendered-00003.png"
    assert output.is_file()
    from PIL import Image

    image = np.asarray(Image.open(output))
    assert image.shape == (32, 32, 3)
    assert image.std() > 5.0

    # 7-phase monotonicity.
    assert timing.started_process_at <= timing.finished_loading_at
    assert timing.started_rendering_at <= timing.finished_rendering_at
    assert timing.file_saving_started_at <= timing.file_saving_finished_at
    assert timing.exited_process_at >= timing.file_saving_finished_at
    assert timing.total_execution_time() > 0


def test_tpu_raytrace_jpeg_output(tmp_path):
    backend = create_backend(
        "tpu-raytrace", base_directory=tmp_path, width=16, height=16, samples=1,
        max_bounces=2,
    )
    job = make_job(tmp_path)
    job = BlenderJob.from_dict({**job.to_dict(), "output_file_format": "JPEG"})
    asyncio.run(backend.render_frame(job, 1))
    assert (tmp_path / "frames" / "rendered-00001.jpg").is_file()


# (job name as the reference's job files spell it, the scene family it
# must resolve to, the job's output format)
JOB_MATRIX = {
    "simple-animation": ("01-simple-animation_measuring_14400f", "01_simple-animation", "JPEG"),
    "physics": ("02_physics_measuring_480f", "02_physics", "JPEG"),
    "physics-2": ("03-physics-2_measuring_480f-10w_naive-fine", "03_physics-2", "JPEG"),
    "physics-mesh": ("02_physics-mesh_e2e", "02_physics-mesh", "JPEG"),
    "physics-2-mesh": ("03_physics-2-mesh_e2e", "03_physics-2-mesh", "PNG"),
}


@pytest.mark.parametrize("family", sorted(JOB_MATRIX))
def test_every_scene_family_renders_end_to_end(family, tmp_path):
    """One frame of each family of the job matrix through the backend: the
    file on disk in the job's format, the seven points in order, the six
    steps, and the frame counted under the one whole-frame tier."""
    from PIL import Image

    from tpu_render_cluster.obs import FRAME_STEPS
    from tpu_render_cluster.render.scene import scene_for_job_name

    job_name, scene, file_format = JOB_MATRIX[family]
    assert scene_for_job_name(job_name) == scene
    backend = create_backend(
        "tpu-raytrace", base_directory=tmp_path, width=24, height=16, samples=1,
        max_bounces=2,
    )
    job = make_job(tmp_path, job_name)
    job = BlenderJob.from_dict({**job.to_dict(), "output_file_format": file_format})
    before = {tier: backend._tier_frames.value(tier=tier) for tier in ("masked", "region", "sharded")}
    timing = asyncio.run(backend.render_frame(job, 2))

    output = tmp_path / "frames" / f"rendered-00002.{'png' if file_format == 'PNG' else 'jpg'}"
    with Image.open(output) as written:
        assert written.format == file_format
        image = np.asarray(written)
    assert image.shape == (16, 24, 3) and image.std() > 5.0
    points = [
        timing.started_process_at, timing.finished_loading_at,
        timing.started_rendering_at, timing.finished_rendering_at,
        timing.file_saving_started_at, timing.file_saving_finished_at,
        timing.exited_process_at,
    ]
    assert points == sorted(points)
    assert {name for name, _, _, _ in timing.steps} == set(FRAME_STEPS)
    after = {tier: backend._tier_frames.value(tier=tier) for tier in before}
    assert {tier: after[tier] - before[tier] for tier in before} == {
        "masked": 1, "region": 0, "sharded": 0,
    }


@pytest.mark.parametrize("flag", ["--wavefront", "--raypool"])
def test_the_worker_cli_has_no_tier_flags(flag, capsys):
    """There is one way to render: the flags that chose another are not
    options any more, and argparse says so."""
    from tpu_render_cluster.worker.main import build_parser

    arguments = [
        "--masterServerHost", "127.0.0.1", "--masterServerPort", "9",
        "--baseDirectory", "/tmp", "--backend", "tpu-raytrace",
    ]
    build_parser().parse_args(arguments)  # the rest of the line is fine
    with pytest.raises(SystemExit) as refused:
        build_parser().parse_args(arguments + [flag, "force"])
    assert refused.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


def test_graft_entry_single_chip():
    import jax

    from __graft_entry__ import entry

    fn, example_args = entry()
    out = jax.jit(fn)(*example_args)
    out.block_until_ready()
    assert out.shape == (128, 128, 3)


def test_graft_dryrun_multichip():
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(8)
