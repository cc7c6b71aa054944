#!/usr/bin/env python3
"""The reading ROADMAP D19 waits for: the shallow mesh family's frame
program on the chip, three ways.

    chiprun -- python scripts/bench-shallow-mesh.py
    JAX_PLATFORMS=cpu TRC_PALLAS=1 python scripts/bench-shallow-mesh.py --rehearse

`02_physics-mesh` (24 boxes of 12 triangles over a 3-node BLAS) at
512x512, 8 spp, 4 bounces, the benchmark's shape, on frames 32 (bodies
falling; a frame the cell's check reads), 120 (bouncing) and 230 (nearly
all at rest):

- `mesh_fused`: the program as served (`fused_frame_renderer`: the mesh
  megakernel, TLAS on);
- `mesh_fused_no_tlas`: the same with `use_tlas=False` (ROADMAP S3 c);
- `mesh_bounce`: the family sent through `_trace_paths_deep`, one
  `mesh_bounce_pallas` launch a bounce, as the deep families are. The gate
  is closed in THIS process alone (`mesh_megakernel_eligible` patched
  here); the program has no switch for it.

Per way and frame: the compile (first call) and the seconds of each of
`REPEATS` later calls, each ended by `block_until_ready`, and the picture's
hash. Then the three pictures of each frame compared: the per-lane random
streams are the same (tests/test_tiles.py), so they agree to rounding; the
line gives the share of equal pixels and the largest difference in levels.
One JSON line per stage on standard output; the same lines in
chiprun_out/shallow_mesh.jsonl. A microbenchmark of the frame program, not
the served path (benchmark/run.py measures that), and it decides nothing:
the numbers go to PERF.md and ROADMAP D19. Off a TPU it exits 2 and times
nothing; `--rehearse` walks through it at 32x32 on whatever device there is
(every line then says `"rehearsal": true`: never times to quote).
"""

from __future__ import annotations

import hashlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from tpu_render_cluster.render import integrator, pallas_kernels  # noqa: E402
from tpu_render_cluster.utils.accelerator import configure_compile_cache  # noqa: E402

SCENE = "02_physics-mesh"
FRAMES = (32, 120, 230)
REPEATS = 5
REHEARSE = "--rehearse" in sys.argv[1:]
SIZE = 32 if REHEARSE else 512
SHAPE = (SIZE, SIZE, 8, 4)  # width, height, samples, bounces
OUT = ROOT / "chiprun_out" / "shallow_mesh.jsonl"


def say(stage: str, **fields) -> None:
    line = json.dumps({"stage": stage, **({"rehearsal": True} if REHEARSE else {}), **fields})
    print(line, flush=True)
    OUT.parent.mkdir(parents=True, exist_ok=True)
    with OUT.open("a") as handle:
        handle.write(line + "\n")


def fresh_programs() -> None:
    integrator.fused_frame_renderer.cache_clear()
    jax.clear_caches()  # a kernel's trace is cached by the function it wraps, not by what that calls


def measure(way: str, **renderer_args) -> dict[int, np.ndarray]:
    """One way's program on every frame: its compile, its times, its pictures."""
    kernel = integrator.scene_trace_kernel(SCENE)
    renderer = integrator.fused_frame_renderer(SCENE, *SHAPE, **renderer_args)
    start = time.perf_counter()
    jax.block_until_ready(renderer(FRAMES[0]))
    say("compile", way=way, kernel=kernel, first_call_s=time.perf_counter() - start)
    pictures = {}
    for frame in FRAMES:
        seconds = []
        for _ in range(REPEATS):
            start = time.perf_counter()
            picture = jax.block_until_ready(renderer(frame))
            seconds.append(time.perf_counter() - start)
        pictures[frame] = np.asarray(picture)
        say(
            "frame", way=way, kernel=kernel, frame=frame, seconds=seconds,
            seconds_median=float(np.median(seconds)),
            picture=hashlib.sha1(pictures[frame].tobytes()).hexdigest()[:12],
        )
    return pictures


def main() -> int:
    configure_compile_cache()
    device = jax.devices()[0]
    say("device", platform=device.platform, kind=device.device_kind, shape=SHAPE, frames=FRAMES)
    if device.platform != "tpu" and not REHEARSE:
        print("bench-shallow-mesh: no TPU here; nothing is timed off the chip", file=sys.stderr)
        return 2
    ways = {
        "mesh_fused": measure("mesh_fused"),
        "mesh_fused_no_tlas": measure("mesh_fused_no_tlas", use_tlas=False),
    }
    gate = pallas_kernels.mesh_megakernel_eligible
    pallas_kernels.mesh_megakernel_eligible = lambda mesh: False  # this process alone
    fresh_programs()
    try:
        ways["mesh_bounce"] = measure("mesh_bounce")
    finally:
        pallas_kernels.mesh_megakernel_eligible = gate
        fresh_programs()
    served = ways["mesh_fused"]
    for way, pictures in ways.items():
        if way == "mesh_fused":
            continue
        for frame in FRAMES:
            apart = np.abs(pictures[frame].astype(np.int16) - served[frame].astype(np.int16)).max(axis=-1)
            say(
                "compare", way=way, against="mesh_fused", frame=frame,
                equal_pixel_share=float((apart == 0).mean()), largest_difference_levels=int(apart.max()),
                pixels_apart_by_more_than_2=int((apart > 2).sum()),
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
