"""Device-resident ray-pool tests (render/raypool.py).

Contracts pinned here:

1. Masked-vs-raypool numeric equivalence on MULTI-FRAME batches (sphere
   + deep-mesh scenes): lanes carry (frame seed, original lane, bounce)
   through the pool's permutation/refill, so per-lane RNG streams and
   physics match the masked per-frame Pallas paths.
2. Scatter-back correctness independent of service order: a frame's
   image is identical whether it rode a batch or rendered alone.
3. Recompile bound: the pool width and frame-window cap are COMPILE-
   TIME config; any batch size reuses one program (render_compiles_total
   grows with pool configs, never with frames or batch sizes).
4. Zero per-bounce host syncs: the exported trace shows one
   raypool_batch span per window and only SYNTHETIC per-iteration spans
   (device-logged occupancy, host-divided timing) — no per-bounce host
   span exists to emit. The artifact passes the trace-invariant checker.
5. The occupancy/refill series flow driver -> registry -> snapshot ->
   obs_events summary, and the worker backend batches its queued frames
   through the pool, serving rendered-ahead frames from cache.

CPU interpret mode is slow, so shapes are tiny.
"""

from __future__ import annotations

import asyncio
import json
import os

import numpy as np
import pytest

os.environ.setdefault("TRC_PALLAS", "0")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

pytestmark = pytest.mark.raypool


def _masked_render(monkeypatch, scene, frame, **kwargs):
    """The masked Pallas reference (megakernel for spheres, per-bounce
    sorted deep path for deep meshes) — same helper shape as
    test_wavefront."""
    from tpu_render_cluster.render.integrator import render_frame

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    out = np.asarray(render_frame(scene, frame, **kwargs))
    jax.clear_caches()
    return out


def _raypool_batch_render(monkeypatch, scene, frames, **kwargs):
    from tpu_render_cluster.render.raypool import render_batch_raypool

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    out = [
        np.asarray(image)
        for image in render_batch_raypool(scene, frames, **kwargs)
    ]
    jax.clear_caches()
    return out


def _assert_images_equivalent(out, ref, *, mae_bound=1e-4):
    lane_diff = np.abs(out - ref).max(axis=-1).ravel()
    n_diverged = int((lane_diff > 2e-3).sum())
    budget = max(1, round(0.001 * lane_diff.size))
    assert n_diverged <= budget, (
        f"{n_diverged}/{lane_diff.size} lanes diverge (budget {budget})"
    )
    mean_abs_error = float(np.abs(out - ref).mean())
    assert mean_abs_error < mae_bound, f"MAE = {mean_abs_error:.2e}"


def test_raypool_matches_masked_sphere_batch(monkeypatch):
    """3-frame sphere batch vs per-frame masked megakernel renders.

    Cross-frame refill means lanes of all three frames coexist in the
    pool; per-(frame, lane) RNG streams and the fid-masked stacked
    scene must keep every frame numerically equivalent to its solo
    masked render.
    """
    kwargs = dict(width=16, height=16, samples=2, max_bounces=3)
    frames = [30, 31, 32]
    refs = [
        _masked_render(monkeypatch, "04_very-simple", f, **kwargs)
        for f in frames
    ]
    outs = _raypool_batch_render(
        monkeypatch, "04_very-simple", frames, **kwargs
    )
    for out, ref in zip(outs, refs):
        _assert_images_equivalent(out, ref)


def test_raypool_matches_masked_mesh_deep_batch(monkeypatch):
    """2-frame deep-mesh batch (127-node BVH x 48 instances x 2 frames
    stacked) vs the masked per-bounce sorted path. The stacked-instance
    frame masking and the per-lane walk limits are what this pins."""
    kwargs = dict(width=12, height=12, samples=1, max_bounces=2)
    frames = [30, 31]
    refs = [
        _masked_render(monkeypatch, "03_physics-2-mesh", f, **kwargs)
        for f in frames
    ]
    outs = _raypool_batch_render(
        monkeypatch, "03_physics-2-mesh", frames, **kwargs
    )
    for out, ref in zip(outs, refs):
        _assert_images_equivalent(out, ref)


def test_raypool_scatter_back_is_service_order_independent(monkeypatch):
    """A frame's buffer only depends on its own rays: batch [30, 31, 32]
    per-frame results equal each frame rendered through a SOLO pool
    (different refill schedule, different blockmates, same scatter
    targets)."""
    kwargs = dict(width=8, height=8, samples=1, max_bounces=2)
    frames = [30, 31, 32]
    batched = _raypool_batch_render(
        monkeypatch, "04_very-simple", frames, **kwargs
    )
    for frame, image in zip(frames, batched):
        solo = _raypool_batch_render(
            monkeypatch, "04_very-simple", [frame], **kwargs
        )[0]
        np.testing.assert_allclose(image, solo, rtol=0, atol=2e-6)


def test_raypool_recompile_bound_across_batch_sizes(monkeypatch):
    """Fixed pool width + frame-window cap => ONE compile across batch
    sizes (the served-ray total is traced, not baked): the compile
    tracker sees exactly one raypool config key, and the jitted pool
    program's cache holds one entry."""
    from tpu_render_cluster.render import raypool
    from tpu_render_cluster.render.compaction import compile_counter

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    kwargs = dict(width=8, height=8, samples=1, max_bounces=2, frame_cap=4)
    before = compile_counter().value()
    for frames in ([40], [41, 42], [43, 44, 45], [46, 47, 48, 49]):
        raypool.render_batch_raypool("04_very-simple", frames, **kwargs)
    assert compile_counter().value() - before == 1, (
        "raypool compile key grew with batch size"
    )
    cache_size = raypool._raypool_batch._cache_size()
    assert cache_size == 1, (
        f"pool program traced {cache_size} times across batch sizes"
    )
    jax.clear_caches()


def test_pool_sort_order_partitions_and_groups_frames():
    """The mesh pool's single permutation: dead lanes strictly after all
    live ones (the kernel's live-count block-skip contract), live lanes
    grouped by frame id, stability within groups."""
    from tpu_render_cluster.render.raypool import _pool_sort_order

    rng = np.random.default_rng(7)
    n = 513
    origins = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    directions = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    alive = jnp.asarray(rng.random(n) < 0.6)
    fid = jnp.asarray(rng.integers(0, 3, size=n).astype(np.int32))
    # One far-away instance AABB so candidates are uniform (isolates the
    # dead/fid key bits).
    lo = jnp.full((1, 3), 500.0, jnp.float32)
    hi = jnp.full((1, 3), 501.0, jnp.float32)
    perm = np.asarray(_pool_sort_order(origins, directions, alive, fid, lo, hi))
    assert sorted(perm.tolist()) == list(range(n))  # a permutation
    alive_np = np.asarray(alive)[perm]
    live = int(np.asarray(alive).sum())
    assert alive_np[:live].all() and not alive_np[live:].any()
    fid_live = np.asarray(fid)[perm][:live]
    # Live lanes group by frame: fids appear as contiguous runs.
    changes = int((np.diff(fid_live) != 0).sum())
    assert changes == len(np.unique(fid_live)) - 1


def test_raypool_zero_per_bounce_syncs_and_valid_trace(monkeypatch, tmp_path):
    """Span/trace inspection of the sync contract: one raypool_batch
    span per window, NO per-bounce host spans (wavefront_bounce is the
    per-bounce-sync driver's signature), per-iteration spans synthetic
    and exactly matching the device iteration count, artifact valid."""
    from tpu_render_cluster.obs import get_tracer, validate_trace_file
    from tpu_render_cluster.render.raypool import render_batch_raypool

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    tracer = get_tracer()
    tracer.clear()
    render_batch_raypool(
        "04_very-simple", [30, 31], width=8, height=8, samples=1,
        max_bounces=3,
    )
    path = tracer.export(tmp_path / "raypool1_trace-events.json")
    assert validate_trace_file(path) == []
    events = json.loads(path.read_text())["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    batch_spans = [e for e in spans if e["name"] == "raypool_batch"]
    assert len(batch_spans) == 1  # 2 frames <= window cap: ONE window
    assert not [e for e in spans if e["name"] == "wavefront_bounce"], (
        "per-bounce host spans present: the pool loop synced per bounce"
    )
    iteration_spans = [e for e in spans if e["name"] == "raypool_iteration"]
    assert iteration_spans, "no per-iteration telemetry spans"
    assert all(
        e["args"].get("synthetic_timing") is True for e in iteration_spans
    ), "iteration spans claim real timing — a host sync would be needed"
    assert len(iteration_spans) == batch_spans[0]["args"]["iterations"]
    # The batch actually exercised multiple bounces' worth of iterations
    # without any per-bounce span: the loop ran device-side.
    assert batch_spans[0]["args"]["iterations"] >= 3
    # Every frame's rays were served and refilled into the pool.
    assert batch_spans[0]["args"]["rays_served"] == 2 * 8 * 8
    tracer.clear()
    jax.clear_caches()


def test_raypool_obs_flow_into_statistics(monkeypatch, tmp_path):
    """Driver -> registry -> snapshot file -> obs_events raypool section."""
    from tpu_render_cluster.analysis.obs_events import (
        load_obs_artifacts,
        summarize_obs,
    )
    from tpu_render_cluster.obs import get_registry, write_metrics_snapshot
    from tpu_render_cluster.render.raypool import (
        raypool_wasted_lane_fraction,
        render_batch_raypool,
    )

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    render_batch_raypool(
        "04_very-simple", [30, 31], width=8, height=8, samples=1,
        max_bounces=2,
    )
    wasted = raypool_wasted_lane_fraction()
    assert wasted is not None and 0.0 <= wasted < 1.0

    write_metrics_snapshot(tmp_path / "run_metrics.json", get_registry())
    traces, metrics = load_obs_artifacts(tmp_path)
    summary = summarize_obs(traces, metrics)
    raypool = summary["raypool"]
    assert raypool["refill_rays_total"] >= 2 * 8 * 8
    assert raypool["iterations_total"] >= 2
    assert 0.0 < raypool["pool_occupancy_mean"] <= 1.0
    assert 0.0 <= raypool["wasted_lane_fraction"] < 1.0
    jax.clear_caches()


class _QueueStub:
    """Captures what the worker queue's hint protocol would pass."""


def test_worker_backend_batches_queue_and_serves_cache(monkeypatch, tmp_path):
    """Backend-level batching: rendering frame 1 with frames 2-3 queued
    renders all three in one pool batch; frames 2-3 then serve from the
    rendered-ahead cache (counted in render_raypool_cache_hits_total)
    and write identical files to what solo renders produce."""
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
    from tpu_render_cluster.obs import get_registry
    from tpu_render_cluster.worker.backends.tpu_raytrace import (
        TpuRaytraceBackend,
    )

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    job = BlenderJob(
        job_name="04_very-simple_raypool",
        job_description=None,
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=3,
        wait_for_number_of_workers=1,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )
    backend = TpuRaytraceBackend(
        base_directory=tmp_path, width=8, height=8, samples=1,
        max_bounces=2, raypool="force",
    )
    backend.note_upcoming_frames(job, (2, 3))
    hits = get_registry().counter(
        "render_raypool_cache_hits_total", ""
    )
    before = hits.value()
    asyncio.run(backend.render_frame(job, 1))
    assert set(backend._raypool_cache) == {
        (job.job_name, 2, None), (job.job_name, 3, None)
    }
    backend.note_upcoming_frames(job, (3,))
    asyncio.run(backend.render_frame(job, 2))
    backend.note_upcoming_frames(job, ())
    asyncio.run(backend.render_frame(job, 3))
    assert hits.value() - before == 2
    assert not backend._raypool_cache
    out_dir = tmp_path / "out"
    batched = {
        p.name: p.read_bytes() for p in sorted(out_dir.glob("*.png"))
    }
    assert len(batched) == 3

    # Solo renders (no queue hint => no batching under "force"? force
    # still pools a 1-frame batch) must produce identical files.
    solo_dir = tmp_path / "solo"
    backend_solo = TpuRaytraceBackend(
        base_directory=tmp_path, width=8, height=8, samples=1,
        max_bounces=2, raypool="force",
    )
    solo_job = BlenderJob(
        job_name=job.job_name,
        job_description=None,
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=3,
        wait_for_number_of_workers=1,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path=str(solo_dir),
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )
    for frame in (1, 2, 3):
        asyncio.run(backend_solo.render_frame(solo_job, frame))
    solo = {p.name: p.read_bytes() for p in sorted(solo_dir.glob("*.png"))}
    assert batched == solo
    jax.clear_caches()


def test_raypool_active_dispatch_tiers(monkeypatch):
    """Env tier + backend flag; auto never engages the pool."""
    from tpu_render_cluster.render.raypool import raypool_active

    monkeypatch.setenv("TRC_PALLAS", "1")
    monkeypatch.delenv("TRC_RAYPOOL", raising=False)
    # auto: off for every scene, whatever is queued ahead.
    assert not raypool_active("03_physics-2-mesh", frames_ahead=2)
    assert not raypool_active("03_physics-2-mesh", frames_ahead=0)
    assert not raypool_active("04_very-simple", frames_ahead=4)
    assert not raypool_active(
        "03_physics-2-mesh", backend_flag="auto", frames_ahead=4
    )
    # env tiers
    monkeypatch.setenv("TRC_RAYPOOL", "0")
    assert not raypool_active("03_physics-2-mesh", frames_ahead=4)
    monkeypatch.setenv("TRC_RAYPOOL", "1")
    assert raypool_active("04_very-simple", frames_ahead=0)
    # backend flag overrides the env tier both ways
    assert not raypool_active(
        "03_physics-2-mesh", backend_flag="off", frames_ahead=4
    )
    monkeypatch.setenv("TRC_RAYPOOL", "0")
    assert raypool_active("04_very-simple", backend_flag="force")
    # pallas off => never
    monkeypatch.setenv("TRC_PALLAS", "0")
    assert not raypool_active("04_very-simple", backend_flag="force")
