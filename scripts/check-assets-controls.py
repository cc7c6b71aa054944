#!/usr/bin/env python3
"""The two controls of configuration `03ph2assets-480f-1w`, through the
benchmark's own check, beside the sound program.

    chiprun -- python scripts/check-assets-controls.py [seed]
    JAX_PLATFORMS=cpu TRC_PALLAS=1 python scripts/check-assets-controls.py --rehearse

For the sound program and for each control it renders the frames the check
of `03ph2assets-1w-queued` looks at for this seed, on the device there is,
through the worker's own backend (so they are written as a worker writes
them), and hands the files to `benchmark/lib/check.py::check_images` as
served frames: the same-stream and independent checks then read them by the
configuration's own crops and limits. Controls:

- `bf16`: the kernels' contractions at the device's default precision (one
  bf16 MXU pass on the chip; on the CPU the operands are rounded to bf16);
- `one_model`: every body given model 0's BLAS.

One JSON line a variant: `{"variant", "correct", "problems", details...}`.
A sound `correct: true` and two controls `correct: false` is the result the
configuration's limits were set to give; the script exits 1 on anything
else. The references are computed once (by the sound variant) and cached.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import check, manifest  # noqa: E402
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy  # noqa: E402
from tpu_render_cluster.render import integrator, pallas_kernels, scene as scene_module  # noqa: E402
from tpu_render_cluster.utils.accelerator import configure_compile_cache  # noqa: E402
from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend  # noqa: E402

CELL = "03ph2assets-1w-queued"
REHEARSE = "--rehearse" in sys.argv[1:]


def bf16_contractions():
    """The kernels' `_dot_f32` without its HIGHEST: the device's default."""
    def dot(a, b, dimension_numbers):
        if jax.devices()[0].platform != "tpu":  # the CPU's default is exact: round the operands
            a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
        return jax.lax.dot_general(a, b, dimension_numbers, preferred_element_type=jnp.float32)

    pallas_kernels._dot_f32 = dot


def one_model_for_all():
    build = scene_module.build_mesh_instances

    def rule(name, frame):
        instances = build(name, frame)
        return instances if instances is None else instances._replace(model=np.zeros_like(instances.model))

    scene_module.build_mesh_instances = rule


def main(argv: list[str]) -> int:
    configure_compile_cache()
    seeds = [int(a) for a in argv if a != "--rehearse"] or [3500001212]
    cell = manifest.load_cell(CELL)
    shape = dict(cell.config["render"])
    if REHEARSE:
        shape.update(width=64, height=64)
        cell.config["render"].update(shape)
    spread = cell.config["frame_range_from"]
    sound_dot, sound_rule = pallas_kernels._dot_f32, scene_module.build_mesh_instances
    verdicts = {}
    for seed in seeds:
        first = spread["first"] + check.mix(seed) % spread["span"]
        frames = check.checked_frames(first, cell.config["frames"], cell.config["check"]["frames"])
        for variant, change in (("sound", None), ("bf16", bf16_contractions), ("one_model", one_model_for_all)):
            pallas_kernels._dot_f32, scene_module.build_mesh_instances = sound_dot, sound_rule
            if change:
                change()
            integrator.fused_frame_renderer.cache_clear()
            jax.clear_caches()  # a bounce launch's trace is cached by the function it wraps, not by what that calls
            with tempfile.TemporaryDirectory() as base:
                backend = TpuRaytraceBackend(
                    base_directory=Path(base), width=shape["width"], height=shape["height"],
                    samples=shape["samples"], max_bounces=shape["max_bounces"],
                )
                job = BlenderJob(
                    job_name="03_physics-2-assets_measuring_480f-1w", job_description=None,
                    project_file_path="%BASE%/p.blend", render_script_path="%BASE%/s.py",
                    frame_range_from=first, frame_range_to=cell.config["frames"], wait_for_number_of_workers=1,
                    frame_distribution_strategy=DistributionStrategy.naive_fine(),
                    output_directory_path="%BASE%/frames", output_file_name_format="rendered-######",
                    output_file_format="JPEG",
                )
                for frame in frames:
                    backend._render_sync(job, frame)
                files = {check.frame_number(path): path for path in (Path(base) / "frames").iterdir()}
                try:
                    problems, details = check.check_images(
                        cell, files, job.job_name, first, cell.config["frames"], seed, dict(os.environ),
                    )
                except (RuntimeError, subprocess.TimeoutExpired) as error:
                    problems, details = [f"image check could not run: {error}"], {}
            verdicts[(seed, variant)] = not problems
            print(json.dumps({
                "variant": variant, "seed": seed, "frames": frames, "device": jax.devices()[0].platform,
                "correct": not problems, "problems": problems, **details,
            }), flush=True)
    as_set = all(ok == (variant == "sound") for (_, variant), ok in verdicts.items())
    return 0 if as_set else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
