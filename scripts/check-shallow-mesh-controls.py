#!/usr/bin/env python3
"""The two controls of configuration `02phmesh-240f-1w`, through the
benchmark's own check, beside the sound program; and the readings its
crops were chosen and its limits kept from.

    chiprun -- python scripts/check-shallow-mesh-controls.py [seed ...]
    JAX_PLATFORMS=cpu TRC_PALLAS=1 python scripts/check-shallow-mesh-controls.py --rehearse

For the sound program and for each control it renders the frames the check
of `02phmesh-1w-queued` looks at (32 and 36, whatever the seed), on the
device there is, through the worker's own backend (so they are JPEG files
as a worker writes them), and hands the files to
`benchmark/lib/check.py::check_images` as served frames: the same-stream
and independent checks then read them by the configuration's own crops and
limits. Controls, each patched in this process alone:

- `no_bodies`: the 24 boxes left out (moved far under the floor, where no
  ray meets them): the picture of the 12 spheres alone;
- `bf16`: the kernels' contractions in ONE ROUNDED bf16 MXU pass
  (`pallas_kernels._bf16_parts` gives a value's rounded top part alone, so
  `_dot_k3_exact` multiplies rounded operands and `_gather_hit` reads
  rounded table rows), and `_dot_f32` without its HIGHEST: the nearest
  precision below the float32 the configuration states.

One JSON line a (seed, variant): `{"variant", "kernel", "correct",
"problems", details...}`, then one `"readings"` line: for EVERY listed crop
(a run's seed picks one), the share of interior pixels within the
configuration's levels on each frame, and the independent check's worst
excess over its own limit (above 0: beyond it). A sound `correct: true`
and two controls `correct: false` on every seed is the result the limits
were set to give; the script exits 1 on anything else. References are
computed once and cached (`benchmark/.cache`); with `--rehearse` everything
is 64x64 on the CPU and proves the path alone (the crops are clamped onto
one another there and need not hold a box).
"""

from __future__ import annotations

import dataclasses
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

from benchmark.lib import check, manifest  # noqa: E402
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy  # noqa: E402
from tpu_render_cluster.render import integrator, pallas_kernels, scene as scene_module  # noqa: E402
from tpu_render_cluster.utils.accelerator import configure_compile_cache  # noqa: E402
from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend  # noqa: E402

CELL = "02phmesh-1w-queued"


def bf16_contractions():
    """One rounded bf16 pass where the kernels split float32 into exact
    parts, and the device's default where they ask for HIGHEST."""
    def rounded_parts(x):
        hi = x.astype(jnp.bfloat16)
        return hi, jnp.zeros_like(hi), jnp.zeros_like(hi)

    def dot(a, b, dimension_numbers):
        if jax.devices()[0].platform != "tpu":  # the CPU's default is exact: round the operands
            a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
        return jax.lax.dot_general(a, b, dimension_numbers, preferred_element_type=jnp.float32)

    pallas_kernels._bf16_parts = rounded_parts
    pallas_kernels._dot_f32 = dot


def bodies_left_out():
    build = scene_module.build_mesh_instances

    def rule(name, frame):
        instances = build(name, frame)
        if instances is None:
            return None
        under = jnp.array([0.0, -1.0e4, 0.0], jnp.float32)  # below the floor: no ray gets there
        return instances._replace(translation=instances.translation + under)

    scene_module.build_mesh_instances = rule


def with_check(cell: manifest.Cell, **changes) -> manifest.Cell:
    """The cell with parts of its `check` block replaced: `same_stream=None`
    drops that check, `same_stream={...}` overrides keys of it."""
    spec = dict(cell.config["check"])
    for key, value in changes.items():
        if value is None:
            spec.pop(key)
        else:
            spec[key] = {**spec[key], **value}
    return dataclasses.replace(cell, config={**cell.config, "check": spec})


def readings(cell, files, job_name, first, last, seed) -> dict:
    """Every listed crop, whichever crop the seed picks."""
    env = dict(os.environ)
    out = {"same_stream": {}, "independent_excess_levels": {}}
    for crop in cell.config["check"]["same_stream"]["crops"]:
        probe = with_check(cell, same_stream={"crops": [crop], "min_share": 0.0}, independent=None)
        _, details = check.check_images(probe, files, job_name, first, last, seed, env)
        out["same_stream"][f"{crop[0]},{crop[1]}"] = details["same_stream"]["agreement"]
    for crop in cell.config["check"]["independent"]["crops"]:
        probe = with_check(cell, same_stream=None, independent={"crops": [crop]})
        _, details = check.check_images(probe, files, job_name, first, last, seed, env)
        out["independent_excess_levels"][f"{crop[0]},{crop[1]}"] = details["independent"]["worst_excess_levels"]
    return out


def main(argv: list[str]) -> int:
    configure_compile_cache()
    rehearse = "--rehearse" in argv
    seeds = [int(a) for a in argv if a != "--rehearse"] or [5600001212]
    cell = manifest.load_cell(CELL)
    if rehearse:
        cell = dataclasses.replace(cell, config={**cell.config, "render": {**cell.config["render"], "width": 64, "height": 64}})
    shape, spread, last = cell.config["render"], cell.config["frame_range_from"], cell.config["frames"]
    sound = pallas_kernels._bf16_parts, pallas_kernels._dot_f32, scene_module.build_mesh_instances
    verdicts = {}
    for seed in seeds:
        first = spread["first"] + check.mix(seed) % spread["span"]
        frames = check.checked_frames(first, last, cell.config["check"]["frames"])
        for variant, change in (("sound", None), ("no_bodies", bodies_left_out), ("bf16", bf16_contractions)):
            pallas_kernels._bf16_parts, pallas_kernels._dot_f32, scene_module.build_mesh_instances = sound
            if change:
                change()
            integrator.fused_frame_renderer.cache_clear()
            jax.clear_caches()  # a kernel's trace is cached by the function it wraps, not by what that calls
            with tempfile.TemporaryDirectory() as base:
                backend = TpuRaytraceBackend(
                    base_directory=Path(base), width=shape["width"], height=shape["height"],
                    samples=shape["samples"], max_bounces=shape["max_bounces"],
                )
                job = BlenderJob(
                    job_name="02_physics-mesh_240f-1w", job_description=None,
                    project_file_path="%BASE%/p.blend", render_script_path="%BASE%/s.py",
                    frame_range_from=first, frame_range_to=last, wait_for_number_of_workers=1,
                    frame_distribution_strategy=DistributionStrategy.naive_fine(),
                    output_directory_path="%BASE%/frames", output_file_name_format="rendered-######",
                    output_file_format=cell.config["output"]["file_format"],
                )
                for frame in frames:
                    backend._render_sync(job, frame)
                files = {check.frame_number(path): path for path in (Path(base) / "frames").iterdir()}
                try:
                    problems, details = check.check_images(
                        cell, files, job.job_name, first, last, seed, dict(os.environ),
                    )
                    read = readings(cell, files, job.job_name, first, last, seed)
                except (RuntimeError, subprocess.TimeoutExpired) as error:
                    problems, details, read = [f"image check could not run: {error}"], {}, {}
            verdicts[(seed, variant)] = not problems
            line = {"variant": variant, "seed": seed, "frames": frames, "device": jax.devices()[0].platform,
                    "kernel": sorted(set(backend.trace_kernels.values())), "correct": not problems, "problems": problems}
            print(json.dumps({**line, **details}), flush=True)
            print(json.dumps({"readings": variant, "seed": seed, **read}), flush=True)
    as_set = all(ok == (variant == "sound") for (_, variant), ok in verdicts.items())
    return 0 if as_set or rehearse else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
