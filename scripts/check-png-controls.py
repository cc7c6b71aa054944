#!/usr/bin/env python3
"""The control of configuration `04vs-14400f-1w-png`, through the
benchmark's own check, beside the sound program; and the readings its
limits were set from.

    chiprun -- python scripts/check-png-controls.py [seed ...]
    JAX_PLATFORMS=cpu TRC_PALLAS=1 python scripts/check-png-controls.py --rehearse

For the sound program and for the control it renders the frames the check
of `04vs-1w-png` looks at for each seed, on the device there is, through
the worker's own backend (so they are PNG files as a worker writes them),
and hands the files to `benchmark/lib/check.py::check_images` as served
frames: the same-stream and independent checks then read them by the
configuration's own crops and limits. The control:

- `bf16`: the sphere megakernel's contractions in ONE ROUNDED bf16 MXU pass
  (`pallas_kernels._bf16_parts` gives a value's rounded top part alone, so
  `_dot_k3_exact` multiplies rounded operands and `_gather_hit` reads
  rounded table rows), and `_dot_f32` without its HIGHEST: the nearest
  precision below the float32 the configuration states.

One JSON line a (seed, variant): `{"variant", "correct", "problems",
details...}`, then one `"readings"` line: for EVERY listed crop (a run's
seed picks one), the share of interior pixels within 0..8 levels on each
frame, and the independent check's worst excess with `abs_levels` 0. A
sound `correct: true` and a control `correct: false` on every seed is the
result the limits were set to give; the script exits 1 on anything else.
References are computed once and cached (`benchmark/.cache`); with
`--rehearse` everything is 64x64 on the CPU and proves the path alone (the
interpreter against itself agrees on every pixel).
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
from tpu_render_cluster.render import integrator, pallas_kernels  # noqa: E402
from tpu_render_cluster.utils.accelerator import configure_compile_cache  # noqa: E402
from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend  # noqa: E402

CELL = "04vs-1w-png"
LEVELS = (0, 1, 2, 3, 4, 6, 8)


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
    """Every listed crop at every level, whichever crop the seed picks."""
    env = dict(os.environ)
    out = {"same_stream": {}, "independent_excess_at_abs_levels_0": {}}
    for crop in cell.config["check"]["same_stream"]["crops"]:
        by_level = {}
        for levels in LEVELS:
            probe = with_check(cell, same_stream={"crops": [crop], "max_levels": levels, "min_share": 0.0}, independent=None)
            _, details = check.check_images(probe, files, job_name, first, last, seed, env)
            by_level[levels] = details["same_stream"]["agreement"]
        out["same_stream"][f"{crop[0]},{crop[1]}"] = by_level
    for crop in cell.config["check"]["independent"]["crops"]:
        probe = with_check(cell, same_stream=None, independent={"crops": [crop], "abs_levels": 0.0})
        _, details = check.check_images(probe, files, job_name, first, last, seed, env)
        out["independent_excess_at_abs_levels_0"][f"{crop[0]},{crop[1]}"] = details["independent"]["worst_excess_levels"]
    return out


def main(argv: list[str]) -> int:
    configure_compile_cache()
    rehearse = "--rehearse" in argv
    seeds = [int(a) for a in argv if a != "--rehearse"] or [5200001212]
    cell = manifest.load_cell(CELL)
    if rehearse:
        cell = dataclasses.replace(cell, config={**cell.config, "render": {**cell.config["render"], "width": 64, "height": 64}})
    shape, spread, last = cell.config["render"], cell.config["frame_range_from"], cell.config["frames"]
    sound = pallas_kernels._bf16_parts, pallas_kernels._dot_f32
    verdicts = {}
    for seed in seeds:
        first = spread["first"] + check.mix(seed) % spread["span"]
        frames = check.checked_frames(first, last, cell.config["check"]["frames"])
        for variant, change in (("sound", None), ("bf16", bf16_contractions)):
            pallas_kernels._bf16_parts, pallas_kernels._dot_f32 = sound
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
                    job_name="04vs_measuring_14400f-1w-png", job_description=None,
                    project_file_path="%BASE%/p.blend", render_script_path="%BASE%/s.py",
                    frame_range_from=first, frame_range_to=last, wait_for_number_of_workers=1,
                    frame_distribution_strategy=DistributionStrategy.naive_fine(),
                    output_directory_path="%BASE%/frames", output_file_name_format="rendered-######",
                    output_file_format=cell.config["output"]["file_format"],
                )
                for frame in frames:
                    backend._render_sync(job, frame)
                files = {check.frame_number(path): path for path in (Path(base) / "frames").iterdir()}
                sizes = {frame: path.stat().st_size for frame, path in sorted(files.items())}
                try:
                    problems, details = check.check_images(
                        cell, files, job.job_name, first, last, seed, dict(os.environ),
                    )
                    read = readings(cell, files, job.job_name, first, last, seed)
                except (RuntimeError, subprocess.TimeoutExpired) as error:
                    problems, details, read = [f"image check could not run: {error}"], {}, {}
            verdicts[(seed, variant)] = not problems
            line = {"variant": variant, "seed": seed, "frames": frames, "device": jax.devices()[0].platform,
                    "file_bytes": sizes, "correct": not problems, "problems": problems}
            print(json.dumps({**line, **details}), flush=True)
            print(json.dumps({"readings": variant, "seed": seed, **read}), flush=True)
    # the interpreter agrees with itself whatever the contractions: a rehearsal proves the path alone
    as_set = all(ok == (variant == "sound") for (_, variant), ok in verdicts.items())
    return 0 if as_set or rehearse else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
