"""Wavefront path tracing tests (render/compaction.py).

Three contracts pinned here:

1. Masked-vs-wavefront equivalence. The wavefront driver keys its
   kernels' counter RNG on the carried ORIGINAL lane id, exactly like
   the masked Pallas paths (the megakernel's positional index IS the
   original lane — it never reorders; the per-bounce deep path threads
   lane ids through its Morton re-sort). Same scene + seed + bounce
   budget must therefore produce the same image up to FP tie-breaking,
   for sphere AND mesh scenes, on the CPU interpret path.
2. Bucketed relaunch bounds recompiles: rendering more frames with
   varying live counts grows the obs ``render_compiles_total`` counter
   only with the bucket ladder, never per frame.
3. The occupancy series flow end to end: driver -> registry ->
   metrics snapshot -> ``analysis/obs_events.summarize_obs``.

Interpret mode on CPU is slow, so shapes are tiny.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TRC_PALLAS", "0")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _masked_render(monkeypatch, scene, **kwargs):
    """The masked Pallas reference: render_frame with TRC_PALLAS forced on
    (megakernel for spheres/shallow meshes, per-bounce sorted deep path
    otherwise)."""
    from tpu_render_cluster.render.integrator import render_frame

    monkeypatch.setenv("TRC_PALLAS", "1")
    jax.clear_caches()
    out = np.asarray(render_frame(scene, 30, **kwargs))
    jax.clear_caches()
    return out


def _assert_images_equivalent(out, ref, *, mae_bound=1e-4):
    """The deep-tree acceptance shape from test_mesh_megakernel: a tight
    per-lane divergence budget (isolated wrong lanes are how culling /
    compaction bugs present) plus an MAE bound (many slightly-wrong
    lanes)."""
    lane_diff = np.abs(out - ref).max(axis=-1).ravel()
    n_diverged = int((lane_diff > 2e-3).sum())
    budget = max(1, round(0.001 * lane_diff.size))
    assert n_diverged <= budget, (
        f"{n_diverged}/{lane_diff.size} lanes diverge (budget {budget})"
    )
    mean_abs_error = float(np.abs(out - ref).mean())
    assert mean_abs_error < mae_bound, f"MAE = {mean_abs_error:.2e}"


def test_wavefront_matches_masked_sphere(monkeypatch):
    """Sphere scene, multi-bounce: wavefront vs the masked megakernel.

    Identical per-original-lane RNG streams on both sides, so this is a
    numeric equivalence (not statistical) despite 3 bounces of sampled
    directions and two rounds of compaction.
    """
    from tpu_render_cluster.render.compaction import render_frame_wavefront

    kwargs = dict(width=16, height=16, samples=2, max_bounces=3)
    ref = _masked_render(monkeypatch, "04_very-simple", **kwargs)
    out = np.asarray(render_frame_wavefront("04_very-simple", 30, **kwargs))
    _assert_images_equivalent(out, ref)


def test_wavefront_matches_masked_mesh_deep(monkeypatch):
    """Deep-walk mesh scene (127-node BVH x 48 instances), multi-bounce.

    The masked side is the per-bounce sorted deep path — the same
    state-io kernel the wavefront driver relaunches, minus the
    compaction — so any divergence beyond FP tie-breaking is a
    lane-threading or live-count bug, not noise.
    """
    from tpu_render_cluster.render.compaction import render_frame_wavefront

    kwargs = dict(width=12, height=12, samples=1, max_bounces=2)
    ref = _masked_render(monkeypatch, "03_physics-2-mesh", **kwargs)
    out = np.asarray(render_frame_wavefront("03_physics-2-mesh", 30, **kwargs))
    _assert_images_equivalent(out, ref)


def test_compaction_order_is_stable_partition():
    from tpu_render_cluster.render.compaction import compaction_order

    rng = np.random.default_rng(11)
    alive = jnp.asarray(rng.random(257) < 0.4)
    perm, live = compaction_order(alive)
    perm = np.asarray(perm)
    n_live = int(np.asarray(live))
    assert n_live == int(np.asarray(alive).sum())
    assert sorted(perm.tolist()) == list(range(257))  # a permutation
    reordered = np.asarray(alive)[perm]
    assert reordered[:n_live].all() and not reordered[n_live:].any()
    # Stability: original relative order preserved within each class.
    assert (np.diff(perm[:n_live]) > 0).all()
    assert (np.diff(perm[n_live:]) > 0).all()


def test_bucket_ladder():
    from tpu_render_cluster.render.compaction import bucket_for

    assert bucket_for(1, cap=8192, block=1024) == 1024
    assert bucket_for(1024, cap=8192, block=1024) == 1024
    assert bucket_for(1025, cap=8192, block=1024) == 2048
    assert bucket_for(5000, cap=8192, block=1024) == 8192
    # Clamped to the wavefront's current width.
    assert bucket_for(5000, cap=4096, block=1024) == 4096
    assert bucket_for(100, cap=640, block=1024) == 640


def _frame_of_rays(n_rays: int, frame: int):
    """Primary rays for a synthetic sphere-scene 'frame' of given width."""
    from tpu_render_cluster.render.camera import camera_rays, scene_camera

    width, height = 64, n_rays // 64
    camera = scene_camera("04_very-simple", frame)
    return camera_rays(
        camera, width, height, y0=0, x0=0,
        tile_height=height, tile_width=width,
        jitter=jnp.full((n_rays, 2), 0.5),
    )


def test_bucketed_relaunch_bounds_recompiles():
    """render_compiles_total grows with the bucket ladder, not frames.

    Frames of 2048 and 1024 rays (so live counts vary across frames and
    bounces) exhaust the whole reachable key set — compaction widths
    {2048, 1024} x bounce buckets {2048, 1024} — after one frame of each
    size; further frames at those sizes, whatever their live counts,
    must not grow the counter.
    """
    from tpu_render_cluster.render.compaction import (
        compile_counter,
        trace_paths_wavefront,
    )
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene("04_very-simple", 1)

    def render(n_rays: int, frame: int):
        origins, directions = _frame_of_rays(n_rays, frame)
        trace_paths_wavefront(
            scene, origins, directions, 1000 + frame, max_bounces=2
        )

    before = compile_counter().value()
    render(2048, 1)
    render(1024, 2)
    after_ladder = compile_counter().value()
    assert after_ladder > before  # the ladder itself did compile
    # <= 2 sizes x (1 compaction width + 1 bounce bucket) keys.
    assert after_ladder - before <= 4
    render(2048, 3)
    render(1024, 4)
    render(2048, 5)
    assert compile_counter().value() == after_ladder, (
        "recompiles grew with frames, not buckets"
    )


def test_occupancy_series_flow_into_statistics(tmp_path):
    """Driver -> registry -> snapshot file -> obs_events summary."""
    from tpu_render_cluster.analysis.obs_events import (
        load_obs_artifacts,
        summarize_obs,
    )
    from tpu_render_cluster.obs import get_registry, write_metrics_snapshot
    from tpu_render_cluster.render.compaction import (
        trace_paths_wavefront,
        wasted_lane_fraction,
    )
    from tpu_render_cluster.render.scene import build_scene

    scene = build_scene("04_very-simple", 1)
    origins, directions = _frame_of_rays(1024, 7)
    trace_paths_wavefront(scene, origins, directions, 99, max_bounces=2)

    wasted = wasted_lane_fraction()
    assert wasted is not None and 0.0 <= wasted < 1.0

    write_metrics_snapshot(tmp_path / "run_metrics.json", get_registry())
    traces, metrics = load_obs_artifacts(tmp_path)
    summary = summarize_obs(traces, metrics)
    wavefront = summary["wavefront"]
    assert wavefront["compiles_total"] >= 1
    assert 0.0 <= wavefront["wasted_lane_fraction"] < 1.0
    assert wavefront["alive_fraction_mean_by_bounce"]["bounce=0"] == pytest.approx(
        1.0
    )
    assert 0.0 < wavefront["lane_occupancy_last"] <= 1.0


def test_wavefront_spans_render_on_dedicated_stable_track(tmp_path):
    """wavefront_bounce spans get their own named Perfetto track with a
    STABLE tid — not the OS-thread tid of whoever happened to drive the
    bounce loop, which interleaved them with unrelated render-phase spans
    and renumbered across runs. The exported artifact must also pass the
    trace-invariant checker."""
    import json

    from tpu_render_cluster.obs import get_tracer, validate_trace_file
    from tpu_render_cluster.render.compaction import trace_paths_wavefront
    from tpu_render_cluster.render.scene import build_scene

    tracer = get_tracer()
    tracer.clear()
    scene = build_scene("04_very-simple", 1)
    origins, directions = _frame_of_rays(1024, 3)
    trace_paths_wavefront(scene, origins, directions, 5, max_bounces=2)

    path = tracer.export(tmp_path / "wf1_trace-events.json")
    assert validate_trace_file(path) == []

    def wavefront_tid(trace_path):
        events = json.loads(trace_path.read_text())["traceEvents"]
        track_tids = {
            e["args"]["name"]: e["tid"]
            for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        assert "wavefront" in track_tids, "wavefront track not named"
        tid = track_tids["wavefront"]
        bounce_spans = [e for e in events if e.get("name") == "wavefront_bounce"]
        assert bounce_spans, "no wavefront_bounce spans recorded"
        assert all(e["tid"] == tid for e in bounce_spans)
        # Dedicated: nothing else renders on the wavefront lane.
        intruders = [
            e for e in events
            if e.get("ph") == "X" and e["tid"] == tid
            and e["name"] != "wavefront_bounce"
        ]
        assert not intruders, intruders
        return tid

    first_tid = wavefront_tid(path)

    # Stability: a later frame in the same process exports with the SAME
    # tid (track assignments survive clear(), so multi-job artifacts from
    # one process line up in the viewer).
    tracer.clear()
    trace_paths_wavefront(scene, origins, directions, 6, max_bounces=2)
    second = tracer.export(tmp_path / "wf2_trace-events.json")
    assert validate_trace_file(second) == []
    assert wavefront_tid(second) == first_tid
    tracer.clear()
