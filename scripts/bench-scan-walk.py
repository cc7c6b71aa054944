#!/usr/bin/env python3
"""On-chip microbenchmark of the streamed BLAS walk: a scene family whose
BLAS is streamed, 03_physics-2-scan unless the family is named
(03_physics-2-assets: three BLASes in one table).

    chiprun -- python scripts/bench-scan-walk.py [family] [samples ...]
    JAX_PLATFORMS=cpu TRC_PALLAS=1 python scripts/bench-scan-walk.py --rehearse 1

For each ``samples`` (default 8 and 1): the frame program's compile and
three frames' times and picture hashes with each bounce launch's (live,
width) and walk counts (``pallas_kernels.WALK_COUNTS``: steps, fetches,
leaf tests, treelet entries, group tests, prefetches); then bounce 0 and bounce 1 alone
at full width (one launch each, rays sorted as the frame program sorts
them), each line with the steps split into box steps (the top's wide
tests, the treelets' wide tests) and leaf tests, the top's tests for each
treelet entered, the mean children hit per wide test inside a treelet, the
share of fetches started one treelet ahead and the microseconds a step;
then one round of the glue the other walk design would pay between launches
(a sort of the ray keys, a packed gather of the ray state, a gather of node
rows).
One JSON line per stage on standard output; the same lines in
chiprun_out/scan_walk.jsonl. A microbenchmark of kernels, not the served
path: benchmark/run.py measures that. Off a TPU it exits 2 and times
nothing; `--rehearse` walks through it at 32x32 on whatever device there is
(every line then says `"rehearsal": true`: counts, never times to quote).
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
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tpu_render_cluster.render import integrator, mesh as mesh_module, pallas_kernels  # noqa: E402
from tpu_render_cluster.render.camera import scene_camera  # noqa: E402
from tpu_render_cluster.render.scene import SCENE_NAMES, build_scene, mesh_kind_for_scene  # noqa: E402
from tpu_render_cluster.utils.accelerator import configure_compile_cache  # noqa: E402

SCENE = next((a for a in sys.argv[1:] if a in SCENE_NAMES), "03_physics-2-scan")
BOUNCES, FRAME = 4, 295
FRAMES = (304, 320, FRAME)  # two the benchmark's check may draw, and the bounces' frame last
# the counts of a tree from before ISSUE 33, so one script reads both sides
WALK_COUNTS = getattr(pallas_kernels, "WALK_COUNTS", ("node_visits", "treelet_fetches"))
REHEARSE = "--rehearse" in sys.argv[1:]
SIZE = 32 if REHEARSE else 512
OUT = ROOT / "chiprun_out" / "scan_walk.jsonl"


def say(stage: str, **fields) -> None:
    line = json.dumps({"stage": stage, **({"rehearsal": True} if REHEARSE else {}), **fields})
    print(line, flush=True)
    OUT.parent.mkdir(parents=True, exist_ok=True)
    with OUT.open("a") as handle:
        handle.write(line + "\n")


def timed(fn, *args, repeats: int = 3):
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - start)
    return out, times


def walk_shape(walk, seconds: float) -> dict:
    """A launch's counts, split: a step is a box test (a wide node's eight
    children at once: of the resident top, a treelet's root or a group) or
    a leaf's triangles; a wide test's children hit are the groups and
    leaves whose turn it caused."""
    counts = dict(zip(WALK_COUNTS, (int(x) for x in walk)))
    steps = counts["node_visits"]
    if "leaf_tests" not in counts:
        return dict(counts, us_per_step=seconds * 1e6 / max(steps, 1))
    leaf_tests = counts["leaf_tests"]
    wide_tests = counts["treelet_entries"] + counts["group_tests"]
    prefetched = (
        {"prefetch_share": counts["treelet_prefetches"] / max(counts["treelet_fetches"], 1)}
        if "treelet_prefetches" in counts else {}  # a tree from before ISSUE 36 has no such count
    )
    return dict(
        counts,
        **prefetched,
        box_steps=steps - leaf_tests,
        top_steps=steps - leaf_tests - wide_tests,
        top_tests_per_entry=(steps - leaf_tests - wide_tests) / max(counts["treelet_entries"], 1),
        wide_tests=wide_tests,
        children_hit_per_wide_test=(counts["group_tests"] + leaf_tests) / max(wide_tests, 1),
        leaf_test_share=leaf_tests / max(steps, 1),
        us_per_step=seconds * 1e6 / max(steps, 1),
    )


def main(argv: list[str]) -> int:
    configure_compile_cache()
    device = jax.devices()[0]
    say("device", platform=device.platform, kind=device.device_kind)
    if device.platform != "tpu" and not REHEARSE:
        print(f"bench-scan-walk: a {device.platform} is not a TPU: nothing timed (--rehearse walks through it)", file=sys.stderr)
        return 2
    start, models = time.perf_counter(), []
    bvh = mesh_module.cached_mesh_bvh(
        mesh_kind_for_scene(SCENE), built=lambda model, triangles, _began, seconds: models.append((model, triangles, seconds))
    )
    stream = mesh_module.scene_blas_stream(SCENE)
    jax.block_until_ready(stream)
    say("bvh_build", scene=SCENE, seconds=time.perf_counter() - start,
        bytes=mesh_module.geometry_bytes(bvh), models=models,
        memory=device.memory_stats() and device.memory_stats().get("bytes_in_use"))

    for samples in [int(a) for a in argv if a.isdigit()] or [8, 1]:
        start = time.perf_counter()
        render = integrator.fused_frame_renderer(SCENE, SIZE, SIZE, samples, BOUNCES, with_live=True)
        out = render(jnp.float32(FRAME))
        jax.block_until_ready(out)
        first = time.perf_counter() - start
        times, pictures = [], {}
        for frame in FRAMES:
            start = time.perf_counter()
            image, live, walk = render(jnp.float32(frame))
            jax.block_until_ready(image)
            times.append(time.perf_counter() - start)
            pictures[frame] = hashlib.sha256(np.asarray(image).tobytes()).hexdigest()[:16]
        say("frame_program", samples=samples, first_call_s=first, frames=FRAMES, frame_s=times,
            live=np.asarray(live).tolist(), walk=np.asarray(walk).tolist(),
            image_std=float(np.asarray(image).std()), image_sha256=pictures,
            peak=device.memory_stats() and device.memory_stats().get("peak_bytes_in_use"))

        # Bounces 0 and 1 alone at full width, sorted as the program sorts.
        scene = build_scene(SCENE, FRAME)
        mesh = mesh_module.scene_mesh_set(SCENE, FRAME, stream=stream)
        camera = scene_camera(SCENE, FRAME)
        base_key = integrator.tile_base_key(jnp.float32(FRAME), 0, 0)
        origins, directions = integrator.flat_sample_rays(
            camera, base_key, width=SIZE, height=SIZE, y0=0, x0=0,
            tile_height=SIZE, tile_width=SIZE, samples=samples,
        )
        n = origins.shape[0]
        alive = jnp.ones((n,), bool)
        order = jnp.argsort(pallas_kernels.initial_mesh_sort_keys(mesh, origins, directions, alive))
        lane = jnp.arange(n, dtype=jnp.int32)[order]
        bounce = jax.jit(
            pallas_kernels.mesh_bounce_pallas, static_argnames=("total_bounces", "use_tlas", "quant"),
        )
        state = (origins[order], directions[order], jnp.ones((n, 3), jnp.float32), alive)
        for index in (0, 1):
            out, times = timed(
                lambda s, i=index: bounce(
                    scene, mesh, *s, 7, jnp.int32(i), total_bounces=BOUNCES, lane=lane,
                    live_count=jnp.sum(s[3], dtype=jnp.int32), use_tlas=True, quant=0,
                ), state,
            )
            _, o2, d2, thr2, alive2, keys, walk = out
            say("bounce_alone", samples=samples, bounce=index, rays=n, seconds=times,
                live_in=int(jnp.sum(state[3])), **walk_shape(np.asarray(walk), min(times)))
            order = jnp.argsort(keys)
            lane = lane[order]
            state = (o2[order], d2[order], thr2[order], alive2[order])

        # One round of design (b)'s glue at this width.
        keys32 = keys
        packed = jnp.concatenate([o2, d2, thr2, thr2], axis=1)
        table = stream.top_boxes.reshape(-1, 8)
        node = (keys32 % table.shape[0]).astype(jnp.int32)
        _, sort_times = timed(jax.jit(jnp.argsort), keys32)
        _, gather_times = timed(jax.jit(lambda p, o: p[o]), packed, order)
        _, node_times = timed(jax.jit(lambda t, i: t[i]), table, node)
        say("design_b_round_glue", samples=samples, rays=n, sort_s=sort_times,
            packed_gather_s=gather_times, node_row_gather_s=node_times)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
