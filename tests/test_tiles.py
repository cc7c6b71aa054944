"""Tile-sharded frames (PR 7): sub-frame work units end to end.

Five contract families, all fast and deterministic (tier-1):

1. **Pixel equivalence** — a master-assembled grid of tile renders equals
   the whole-frame render (sphere megakernel via the lane_io fused
   kernel, both mesh scenes via the per-bounce kernel), on the CPU
   interpret path with TRC_PALLAS forced on, compared at the uint8
   output level against the production fused whole-frame renderer.
2. **Assembly exactly-once** — the frame-complete transition fires once
   per frame regardless of duplicate/late copies of the final tile, and
   the stitcher reproduces the frame from tile files (removing them).
3. **Scheduling at tile grain** — steal and preemption of a single tile
   unit move exactly that unit; the queue mirror keys on
   (job, frame, tile) with no index-only fallback.
4. **Wire** — whole-frame traffic is byte-identical to pre-tiling
   (no ``tile`` key anywhere); tiled payloads round-trip.
5. **End to end** — a 2-worker tiled cluster over real sockets completes
   with an exact per-tile ledger and clean mirrors; a tiled
   tpu-raytrace cluster's stitched output file is pixel-identical to an
   untiled run's.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np
import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.jobs.tiles import (
    WorkUnit,
    parse_tile_grid,
    tile_bounds,
    tile_rc,
)
from tpu_render_cluster.master.queue_mirror import FrameOnWorker, WorkerQueueMirror
from tpu_render_cluster.master.state import ClusterManagerState, FrameStatus
from tpu_render_cluster.master.strategies import preempt_frame, steal_frame
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.worker.backends.base import RenderBackend

pytestmark = pytest.mark.tiles


def make_job(
    frames: int = 2,
    workers: int = 1,
    grid: tuple[int, int] | None = (2, 2),
    name: str = "tiles-unit",
    output_directory: str = "%BASE%/out",
) -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description="tile unit test",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=workers,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path=output_directory,
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
        tile_grid=grid,
    )


# ---------------------------------------------------------------------------
# Tile model


class TestTileModel:
    def test_bounds_partition_the_frame(self):
        # Non-divisible dims: tiles must still tile the frame exactly.
        grid = (3, 2)
        covered = np.zeros((17, 13), dtype=int)
        for tile in range(6):
            y0, x0, th, tw = tile_bounds(tile, grid, width=13, height=17)
            assert th > 0 and tw > 0
            covered[y0 : y0 + th, x0 : x0 + tw] += 1
        assert (covered == 1).all()

    def test_tile_rc_row_major(self):
        assert tile_rc(0, (2, 3)) == (0, 0)
        assert tile_rc(3, (2, 3)) == (1, 0)
        assert tile_rc(5, (2, 3)) == (1, 2)
        with pytest.raises(ValueError):
            tile_rc(6, (2, 3))

    def test_parse_tile_grid(self):
        assert parse_tile_grid("2x2") == (2, 2)
        assert parse_tile_grid("2,3") == (2, 3)
        assert parse_tile_grid("4") == (4, 4)
        with pytest.raises(ValueError):
            parse_tile_grid("0x2")
        with pytest.raises(ValueError):
            parse_tile_grid("17x1")

    def test_job_units_and_serde(self):
        job = make_job(frames=2, grid=(2, 2))
        units = list(job.work_units())
        assert len(units) == 8 == job.unit_count()
        assert units[0] == WorkUnit(1, 0) and units[7] == WorkUnit(2, 3)
        decoded = BlenderJob.from_dict(job.to_dict())
        assert decoded.tile_grid == (2, 2)
        # Untiled jobs serialize with no tiles key at all.
        assert "tiles" not in make_job(grid=None).to_dict()

    def test_env_grid_applies_at_load_time_only(self, tmp_path, monkeypatch):
        path = tmp_path / "job.toml"
        path.write_text(
            "\n".join(
                f'{k} = "{v}"' if isinstance(v, str) else f"{k} = {v}"
                for k, v in (
                    ("job_name", "env-grid"),
                    ("project_file_path", "p.blend"),
                    ("render_script_path", "s.py"),
                    ("frame_range_from", 1),
                    ("frame_range_to", 2),
                    ("wait_for_number_of_workers", 1),
                    ("output_directory_path", "out"),
                    ("output_file_name_format", "r-####"),
                    ("output_file_format", "PNG"),
                )
            )
            + '\n[frame_distribution_strategy]\nstrategy_type = "naive-fine"\n',
            encoding="utf-8",
        )
        monkeypatch.setenv("TRC_TILE_GRID", "2x2")
        job = BlenderJob.load_from_file(path)
        assert job.tile_grid == (2, 2)
        # The WIRE decoder must never consult the environment: a worker
        # with the env set cannot reinterpret an untiled job.
        assert BlenderJob.from_dict(make_job(grid=None).to_dict()).tile_grid is None

    def test_invalid_grid_rejected(self):
        with pytest.raises(ValueError, match="tile grid"):
            make_job(grid=(0, 2))
        with pytest.raises(ValueError, match="tile grid"):
            make_job(grid=(1, 99))
        # Malformed shapes land in the aggregated 'Invalid job' report,
        # not a bare int() traceback — and a string never iterates into
        # a grid.
        for bad in ("2x2", "22", [2, "a"], [2], 4):
            with pytest.raises(ValueError, match="Invalid job.*tiles"):
                BlenderJob.from_dict({**make_job(grid=None).to_dict(), "tiles": bad})


# ---------------------------------------------------------------------------
# Wire: whole-frame byte-identity + tile round-trip


class TestTileWire:
    def test_whole_frame_traffic_byte_identical(self):
        """Untiled jobs produce EXACTLY the pre-PR wire bytes: no tile
        key on the add/remove requests, either frame event, or the
        goodbye — and the job dict carries no tiles key."""
        job = make_job(grid=None, name="wire-whole")
        add = pm.MasterFrameQueueAddRequest(1234, job, 1)
        payload = json.loads(pm.encode_message(add))["payload"]
        assert "tile" not in payload
        assert "tiles" not in payload["job"]
        remove = pm.MasterFrameQueueRemoveRequest(1234, "wire-whole", 1)
        assert "tile" not in remove.to_payload()
        assert remove.to_payload() == {
            "message_request_id": 1234,
            "job_name": "wire-whole",
            "frame_index": 1,
        }
        for event in (
            pm.WorkerFrameQueueItemRenderingEvent("wire-whole", 1),
            pm.WorkerFrameQueueItemFinishedEvent.new_ok("wire-whole", 1),
        ):
            assert "tile" not in event.to_payload()
        goodbye = pm.WorkerGoodbyeEvent(
            job_name="wire-whole", returned_frames=(2, 3),
            returned_tiles=(None, None),
        )
        assert "returned_tiles" not in goodbye.to_payload()

    def test_tile_round_trips(self):
        job = make_job(name="wire-tiled")
        add = pm.MasterFrameQueueAddRequest.new(job, 1, tile=3)
        decoded = pm.decode_message(pm.encode_message(add))
        assert decoded.tile == 3 and decoded.job.tile_grid == (2, 2)
        remove = pm.MasterFrameQueueRemoveRequest.new("wire-tiled", 1, tile=2)
        assert pm.decode_message(pm.encode_message(remove)).tile == 2
        event = pm.WorkerFrameQueueItemFinishedEvent.new_ok(
            "wire-tiled", 1, tile=0
        )
        assert pm.decode_message(pm.encode_message(event)).tile == 0
        goodbye = pm.WorkerGoodbyeEvent(
            job_name="wire-tiled", returned_frames=(2, 2),
            returned_tiles=(0, 3),
        )
        decoded = pm.decode_message(pm.encode_message(goodbye))
        assert decoded.returned_tiles == (0, 3)

    def test_malformed_tile_rejected(self):
        with pytest.raises(ValueError, match="tile"):
            pm.MasterFrameQueueRemoveRequest.from_payload(
                {"message_request_id": 1, "job_name": "x", "frame_index": 1,
                 "tile": "zero"}
            )


# ---------------------------------------------------------------------------
# Mirror: (job, frame, tile) key, no index-only fallback


class TestTileMirror:
    def test_tiles_coexist_and_remove_exactly_one(self):
        mirror = WorkerQueueMirror()
        for tile in range(4):
            mirror.add(
                FrameOnWorker(1, queued_at=1.0, job_name="j", tile=tile)
            )
        assert len(mirror) == 4
        assert mirror.remove(1, "j", 2).tile == 2
        assert mirror.remove(1, "j", 2) is None
        assert len(mirror) == 3
        # Whole-frame key is NOT a wildcard.
        assert mirror.get(1, "j") is None

    def test_set_rendering_is_tile_exact(self):
        mirror = WorkerQueueMirror()
        mirror.add(FrameOnWorker(1, queued_at=1.0, job_name="j", tile=0))
        mirror.add(FrameOnWorker(1, queued_at=1.0, job_name="j", tile=1))
        mirror.set_rendering(1, "j", 1)
        states = {f.tile: f.is_rendering for f in mirror.all_frames()}
        assert states == {0: False, 1: True}


# ---------------------------------------------------------------------------
# Assembly exactly-once


class TestAssemblyLedger:
    def test_frame_completes_exactly_once(self):
        state = ClusterManagerState(make_job(frames=1, grid=(2, 2)))
        completions = [
            state.mark_frame_as_finished(WorkUnit(1, tile))
            for tile in range(4)
        ]
        # Only the LAST tile completes the frame.
        assert completions == [False, False, False, True]
        # A duplicate of the final tile cannot re-complete it.
        assert state.mark_frame_as_finished(WorkUnit(1, 3)) is False
        assert state.all_frames_finished()
        state.note_frame_assembled(1)
        assert state.frames_assembled == 1
        assert state.partially_assembled_frames() == []

    def test_partial_frames_reported(self):
        state = ClusterManagerState(make_job(frames=2, grid=(2, 2)))
        state.mark_frame_as_finished(WorkUnit(1, 0))
        assert state.partially_assembled_frames() == [1]
        assert state.tiles_landed(1) == 1
        assert state.assembly_view()["frames_partial"] == 1

    def test_whole_frame_jobs_complete_per_unit(self):
        state = ClusterManagerState(make_job(frames=2, grid=None))
        assert state.mark_frame_as_finished(WorkUnit(1)) is True
        assert state.mark_frame_as_finished(WorkUnit(1)) is False

    def test_stitcher_reassembles_and_cleans_up(self, tmp_path):
        from PIL import Image

        from tpu_render_cluster.master.assembly import assemble_frame_files
        from tpu_render_cluster.render.image_io import output_path_for_tile

        job = make_job(
            frames=1, grid=(2, 2), output_directory=str(tmp_path)
        )
        rng = np.random.default_rng(5)
        full = rng.integers(0, 255, size=(10, 14, 3), dtype=np.uint8)
        for tile in range(4):
            y0, x0, th, tw = tile_bounds(tile, (2, 2), width=14, height=10)
            path = output_path_for_tile(
                tmp_path, "rendered-#####", "PNG", 1, tile, (2, 2)
            )
            path.parent.mkdir(parents=True, exist_ok=True)
            Image.fromarray(full[y0 : y0 + th, x0 : x0 + tw]).save(path, "PNG")
        frame_path = assemble_frame_files(job, 1)
        assert frame_path is not None and frame_path.exists()
        stitched = np.asarray(Image.open(frame_path).convert("RGB"))
        assert np.array_equal(stitched, full)
        # Tile intermediates are removed after the stitch.
        assert not list(tmp_path.glob("*.tile_*"))

    def test_stitcher_tolerates_no_tiles_and_flags_partial(self, tmp_path):
        from PIL import Image

        from tpu_render_cluster.master.assembly import assemble_frame_files
        from tpu_render_cluster.render.image_io import output_path_for_tile

        job = make_job(frames=1, grid=(2, 2), output_directory=str(tmp_path))
        # Mock-backend clusters: no tile files at all -> None, no error.
        assert assemble_frame_files(job, 1) is None
        # A PARTIAL grid is a bug worth surfacing.
        path = output_path_for_tile(
            tmp_path, "rendered-#####", "PNG", 1, 0, (2, 2)
        )
        path.parent.mkdir(parents=True, exist_ok=True)
        Image.fromarray(np.zeros((5, 7, 3), np.uint8)).save(path, "PNG")
        with pytest.raises(FileNotFoundError, match="tile"):
            assemble_frame_files(job, 1)


# ---------------------------------------------------------------------------
# Steal / preempt at tile grain


class _FakeWorker:
    def __init__(self, worker_id, state):
        self.worker_id = worker_id
        self.state = state
        self.is_dead = False
        self.frames_stolen_count = 0
        self.queue = WorkerQueueMirror()
        self.queued_units: list[WorkUnit] = []

    async def unqueue_frame(self, job_name, unit):
        if self.queue.get(unit.frame_index, job_name, unit.tile) is None:
            return pm.FRAME_QUEUE_REMOVE_RESULT_ERRORED
        self.queue.remove(unit.frame_index, job_name, unit.tile)
        return pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED

    async def queue_frame(self, job, unit, *, stolen_from=None, job_id=None, trigger=None):
        self.queued_units.append(unit)
        now = time.time()
        self.queue.add(
            FrameOnWorker(
                unit.frame_index, queued_at=now, job_name=job.job_name,
                tile=unit.tile,
            )
        )
        self.state.mark_frame_as_queued(
            unit, self.worker_id, now, stolen_from=stolen_from
        )


class TestTileStealPreempt:
    def _setup(self):
        job = make_job(frames=1, grid=(2, 2))
        state = ClusterManagerState(job)
        thief = _FakeWorker(0x1001, state)
        victim = _FakeWorker(0x1002, state)
        now = time.time()
        for tile in range(4):
            unit = state.next_pending_unit()
            assert unit == WorkUnit(1, tile)
            state.mark_frame_as_queued(unit, victim.worker_id, now)
            victim.queue.add(
                FrameOnWorker(
                    1, queued_at=now, job_name=job.job_name, tile=tile
                )
            )
        return job, state, thief, victim

    def test_steal_moves_exactly_one_tile(self):
        async def scenario():
            job, state, thief, victim = self._setup()
            unit = WorkUnit(1, 2)
            assert await steal_frame(job, state, thief, victim, unit) is True
            assert thief.queued_units == [unit]
            assert state.frames[unit].worker_id == thief.worker_id
            # The victim keeps its other three tiles of the SAME frame.
            remaining = sorted(f.tile for f in victim.queue.all_frames())
            assert remaining == [0, 1, 3]
            for tile in remaining:
                assert (
                    state.frames[WorkUnit(1, tile)].worker_id
                    == victim.worker_id
                )

        asyncio.run(scenario())

    def test_preempt_returns_tile_to_its_pool(self):
        async def scenario():
            job, state, thief, victim = self._setup()
            unit = WorkUnit(1, 1)
            assert await preempt_frame(job, state, victim, unit) is True
            assert state.frames[unit].status is FrameStatus.PENDING
            assert state.next_pending_unit() == unit
            assert sorted(f.tile for f in victim.queue.all_frames()) == [0, 2, 3]

        asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Pixel equivalence, tiles against the whole frame (Pallas interpret)


def _clear_jax_caches():
    import jax

    jax.clear_caches()
    from tpu_render_cluster.render.integrator import (
        fused_frame_renderer,
        fused_region_renderer,
    )

    fused_frame_renderer.cache_clear()
    fused_region_renderer.cache_clear()


@pytest.fixture()
def _pallas_interpret(monkeypatch):
    monkeypatch.setenv("TRC_PALLAS", "1")
    _clear_jax_caches()
    yield
    _clear_jax_caches()


SPHERE_KW = dict(width=16, height=16, samples=2, max_bounces=3)
MESH_KW = dict(width=12, height=12, samples=1, max_bounces=2)
# 3 does not divide 14 rows: the 3x2 grid has a ragged edge (tiles of 4, 5
# and 5 rows: two region programs); the other grids compile one.
RAGGED_MESH_KW = dict(width=16, height=14, samples=1, max_bounces=2)

# The whole frame of a case, kept for the other grids of its scene: the
# fixture below drops every compiled program between tests.
_WHOLE_FRAMES: dict[tuple, np.ndarray] = {}


def _stitched_and_whole(scene, kw, grid, frame):
    """The grid's fused-region tiles stitched, and the production
    whole-frame renderer's output, both as the worker writes them (u8)."""
    from tpu_render_cluster.render.integrator import (
        fused_frame_renderer,
        render_frame_region,
        tonemap,
    )

    height, width = kw["height"], kw["width"]
    key = (scene, frame, *sorted(kw.items()))
    if key not in _WHOLE_FRAMES:
        _WHOLE_FRAMES[key] = np.asarray(
            fused_frame_renderer(
                scene, width, height, kw["samples"], kw["max_bounces"]
            )(frame)
        )
    whole = _WHOLE_FRAMES[key]
    stitched = np.zeros_like(whole)
    for tile in range(grid[0] * grid[1]):
        y0, x0, th, tw = tile_bounds(tile, grid, width=width, height=height)
        stitched[y0 : y0 + th, x0 : x0 + tw] = np.asarray(
            tonemap(
                render_frame_region(
                    scene, frame, y0=y0, x0=x0, tile_height=th,
                    tile_width=tw, width=width, height=height,
                    samples=kw["samples"], max_bounces=kw["max_bounces"],
                )
            )
        )
    return stitched, whole


class TestTileEquivalence:
    @pytest.mark.parametrize("grid", [(2, 2), (1, 4), (3, 2)], ids=["2x2", "1x4", "3x2"])
    @pytest.mark.parametrize(
        "scene,kw",
        [
            ("04_very-simple", SPHERE_KW),
            ("02_physics-mesh", RAGGED_MESH_KW),
            ("03_physics-2-mesh", RAGGED_MESH_KW),
        ],
        ids=["sphere", "shallow-mesh", "deep-mesh"],
    )
    def test_masked_tier_assembles_identically(
        self, _pallas_interpret, scene, kw, grid
    ):
        """Stitched fused-region tiles == the production whole-frame
        renderer's uint8 output, whatever the grid: the region program is
        the only way a tile is rendered. (The shallow mesh's whole frame is
        the megakernel and its tiles the per-bounce kernel: same streams.)"""
        stitched, whole = _stitched_and_whole(scene, kw, grid, 30)
        assert np.array_equal(stitched, whole)

    def test_masked_tier_assembles_identically_as_the_scene_settles(
        self, _pallas_interpret
    ):
        """One compiled region program per tile shape serves every frame:
        a falling frame and a settled one of the deep scene, whose bounces
        run at different rungs of the launch-width ladder."""
        for frame in (30, 295):
            stitched, whole = _stitched_and_whole(
                "03_physics-2-mesh", MESH_KW, (2, 2), frame
            )
            assert np.array_equal(stitched, whole), frame


# ---------------------------------------------------------------------------
# End to end


class TestTiledClusterE2E:
    def test_mock_cluster_completes_with_exact_tile_ledger(self):
        """2 workers, 2 frames x 2x2 tiles over real sockets: every unit
        exactly once, both workers served tiles, mirrors swept, and the
        per-frame assembly ledger full."""
        from tpu_render_cluster.chaos.invariants import check_tile_invariants
        from tpu_render_cluster.harness.local import _run_local_job_full
        from tpu_render_cluster.worker.backends.mock import MockBackend

        job = make_job(frames=2, workers=2, grid=(2, 2), name="tiles-e2e")
        backends = [MockBackend(render_seconds=0.01) for _ in range(2)]
        _trace, _worker_traces, manager, _workers = _run_local_job_full(
            job, backends, 120.0
        )
        state = manager.state
        assert state.all_frames_finished()
        assert len(state.frames) == 8
        assert state.ledger["ok_results"] - state.ledger["duplicate_results"] == 8
        assert state.frames_assembled == 2
        assert check_tile_invariants(state) == []
        for worker in manager.workers.values():
            assert len(worker.queue) == 0
        # Both workers rendered tile units (the load actually spread).
        rendered = [len(b.rendered_units) for b in backends]
        assert sum(rendered) == 8 and all(n > 0 for n in rendered)
        assert all(
            tile is not None for b in backends for _, tile in b.rendered_units
        )

    def test_tpu_raytrace_tiled_output_matches_untiled(
        self, tmp_path, _pallas_interpret
    ):
        """The full pipeline: tiled workers write tile files, the master
        stitches — the final frame PNG is pixel-identical to an untiled
        run's (the bench's seam check, pinned as a test)."""
        from PIL import Image

        from tpu_render_cluster.harness.local import run_local_job
        from tpu_render_cluster.worker.backends.tpu_raytrace import (
            TpuRaytraceBackend,
        )

        outputs = {}
        for label, grid, workers in (("whole", None, 1), ("tiled", (2, 2), 2)):
            out = tmp_path / label
            job = make_job(
                frames=1, workers=workers, grid=grid,
                name=f"04_very-simple_seam-{label}",
                output_directory=str(out),
            )
            backends = [
                TpuRaytraceBackend(width=16, height=16, samples=2, max_bounces=3)
                for _ in range(workers)
            ]
            run_local_job(job, backends, timeout=600.0)
            outputs[label] = out / "rendered-00001.png"
        whole = np.asarray(Image.open(outputs["whole"]).convert("RGB"))
        tiled = np.asarray(Image.open(outputs["tiled"]).convert("RGB"))
        assert np.array_equal(whole, tiled)
        # The tile intermediates were cleaned up by the stitcher.
        assert not list((tmp_path / "tiled").glob("*.tile_*"))


class _AlwaysFailBackend(RenderBackend):
    """A backend that deterministically cannot render (the Blender-backend
    tiled-unit shape)."""

    async def render_frame(self, job, frame_index, tile=None):
        raise RuntimeError("this backend cannot render sub-frame tiles")


def test_deterministic_unit_error_fails_the_job(monkeypatch):
    """A unit that errors on every attempt must FAIL the job after the
    error budget (TRC_MAX_UNIT_ERRORS), not redispatch in a hot loop
    forever — the tiled-job-on-a-Blender-cluster case."""
    from tpu_render_cluster.harness.local import run_local_job

    monkeypatch.setenv("TRC_MAX_UNIT_ERRORS", "3")
    job = make_job(frames=1, workers=1, grid=(2, 2), name="tiles-fail")
    with pytest.raises(RuntimeError, match="errored 3 times"):
        run_local_job(job, [_AlwaysFailBackend()], timeout=60.0)


# ---------------------------------------------------------------------------
# Chaos at tile grain (fast seeded run; also part of the chaos suite)


@pytest.mark.chaos
def test_seeded_tiled_chaos_run_holds_tile_invariants():
    """One seeded multi-worker TILED chaos run: the full fault schedule
    races steals/evictions/duplicates against sub-frame units, audited
    at tile granularity (ok_tiles - duplicate_tiles == tiles_total per
    job, no partially-assembled ghost frames)."""
    from tpu_render_cluster.chaos.plan import FaultPlan
    from tpu_render_cluster.chaos.runner import run_chaos_job

    plan = FaultPlan.generate(7, 3)
    report = run_chaos_job(plan, frames=3, tile_grid=(2, 2), timeout=150.0)
    assert report.ok, report.violations
    assert report.stats["frames_total"] == 12  # 3 frames x 4 tiles
    assert report.stats["tiles_per_frame"] == 4
    assert report.stats["frames_assembled"] == 3
