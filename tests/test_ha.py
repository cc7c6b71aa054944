"""Replicated control plane tests: write-ahead ledger, epoch fencing,
master failover, and the shard router.

The fast deterministic subset runs in tier-1: ledger append/replay round
trips (including crash-torn tails — the recovery contract the ISSUE
names), the epoch fence at both ends of the wire, one full seeded
master-failover acceptance run (primary killed mid-job, standby replays
the ledger and completes it with the cross-incarnation exactly-once
audit green), and a 2-shard router e2e over real control sockets.
"""

import asyncio
import json
import logging
from pathlib import Path

import pytest

from tpu_render_cluster.chaos.invariants import counter_total
from tpu_render_cluster.chaos.plan import (
    KIND_FOLLOWER_LAG,
    KIND_MASTER_KILL,
    KIND_MASTER_PARTITION,
    KIND_REPLICATION_PARTITION,
    KIND_ROUTER_KILL,
    MASTER_TARGET,
    REPLICATION_KINDS,
    FaultPlan,
)
from tpu_render_cluster.ha.chaos import (
    run_chaos_failover_job,
    run_chaos_replicated_failover,
    run_chaos_shard_kill,
)
from tpu_render_cluster.ha.failover import apply_ledger_to_state
from tpu_render_cluster.ha.ledger import (
    JobLedger,
    LedgerCorruptError,
    LedgerReplay,
)
from tpu_render_cluster.ha.replicate import (
    LedgerFollower,
    ReplicationServer,
    _encode_line,
)
from tpu_render_cluster.ha.shards import (
    ShardRouter,
    ShardRouterServer,
    shard_for_job_name,
    split_routed_job_id,
)
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.master.resume import apply_resume
from tpu_render_cluster.master.state import ClusterManagerState, FrameStatus
from tpu_render_cluster.obs import MetricsRegistry, validate_trace_file
from tpu_render_cluster.obs.prometheus import lint_metric
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.sched.rebalance import Move, RebalancePlanner, ShardLoad

pytestmark = pytest.mark.ha

ACCEPTANCE_SEED = 99


def make_job(name="ha-job", frames=6, workers=1, tile_grid=None):
    return BlenderJob(
        job_name=name,
        job_description="ha test",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=workers,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
        tile_grid=tile_grid,
    )


# ---------------------------------------------------------------------------
# Write-ahead ledger: append / replay / segments / snapshots


def test_ledger_append_replay_roundtrip(tmp_path):
    ledger = JobLedger.open(tmp_path)
    assert ledger.epoch == 1
    ledger.append_job_started(
        "j1", spec={"x": 1}, job_id="job-0001", weight=2.0, priority=3
    )
    for frame in range(4):
        ledger.append_unit_finished("j1", frame)
    ledger.append_unit_finished("j1", 9, tile=2)
    ledger.close()

    replay = JobLedger.replay_directory(tmp_path)
    entry = replay.job("j1")
    assert entry.finished_units == {(0, None), (1, None), (2, None), (3, None), (9, 2)}
    assert entry.job == {"x": 1}
    assert entry.job_id == "job-0001"
    assert (entry.weight, entry.priority, entry.status) == (2.0, 3, "started")
    assert replay.unfinished_jobs() == [entry]
    assert not replay.torn_tail


def test_ledger_epoch_monotonic_across_opens(tmp_path):
    epochs = []
    for _ in range(3):
        ledger = JobLedger.open(tmp_path)
        epochs.append(ledger.epoch)
        ledger.close()
    assert epochs == [1, 2, 3]
    assert JobLedger.peek_epoch(tmp_path) == 3


def test_ledger_torn_final_record_recovers(tmp_path):
    """Crash mid-append: a torn final record is dropped, recovering to
    the last complete record — and the next open repairs the tail so the
    damage cannot be mistaken for corruption later."""
    ledger = JobLedger.open(tmp_path)
    ledger.append_job_started("j1")
    ledger.append_unit_finished("j1", 1)
    ledger.append_unit_finished("j1", 2)
    ledger.close()
    segment = sorted(tmp_path.glob("segment-*.jsonl"))[-1]
    with open(segment, "ab") as f:
        f.write(b'{"v":1,"seq":99,"type":"unit_finished","job":"j1","fra')

    replay = JobLedger.replay_directory(tmp_path)
    assert replay.torn_tail
    assert replay.finished_units("j1") == {(1, None), (2, None)}

    # Open repairs the tail and appends cleanly after it.
    ledger = JobLedger.open(tmp_path)
    ledger.append_unit_finished("j1", 3)
    ledger.close()
    replay = JobLedger.replay_directory(tmp_path)
    assert not replay.torn_tail
    assert replay.finished_units("j1") == {(1, None), (2, None), (3, None)}


def test_ledger_complete_record_missing_only_newline_is_kept(tmp_path):
    """A final line that parses but lost its newline is a COMPLETE record;
    it must be replayed, not dropped — and the next open() must REPAIR
    the missing newline, or the segment (no longer final once appends
    open a new one) would read as corrupt at the restart after that."""
    ledger = JobLedger.open(tmp_path)
    ledger.append_job_started("j1")
    ledger.append_unit_finished("j1", 1)
    ledger.close()
    segment = sorted(tmp_path.glob("segment-*.jsonl"))[-1]
    raw = segment.read_bytes()
    segment.write_bytes(raw.rstrip(b"\n"))
    replay = JobLedger.replay_directory(tmp_path)
    assert not replay.torn_tail
    assert replay.finished_units("j1") == {(1, None)}
    # Survive TWO reopens: open #1 repairs the tail and appends into a
    # fresh segment; open #2 must replay the (now non-final) segment
    # cleanly instead of refusing it as torn.
    ledger = JobLedger.open(tmp_path)
    assert segment.read_bytes().endswith(b"\n")
    ledger.append_unit_finished("j1", 2)
    ledger.close()
    replay = JobLedger.replay_directory(tmp_path)
    assert replay.finished_units("j1") == {(1, None), (2, None)}


def test_ledger_malformed_mid_segment_is_corruption(tmp_path):
    ledger = JobLedger.open(tmp_path)
    ledger.append_job_started("j1")
    ledger.append_unit_finished("j1", 1)
    ledger.close()
    segment = sorted(tmp_path.glob("segment-*.jsonl"))[-1]
    lines = segment.read_bytes().split(b"\n")
    lines[0] = b'{"torn": tru'
    segment.write_bytes(b"\n".join(lines))
    with pytest.raises(LedgerCorruptError, match="non-tail"):
        JobLedger.replay_directory(tmp_path)


def test_ledger_refuses_future_format(tmp_path):
    ledger = JobLedger.open(tmp_path)
    ledger.append_job_started("j1")
    ledger.close()
    (tmp_path / "segment-99999999.jsonl").write_text(
        '{"v":2,"seq":1000,"type":"unit_finished","job":"j1","frame":9}\n'
    )
    with pytest.raises(LedgerCorruptError, match="future format"):
        JobLedger.replay_directory(tmp_path)


def test_ledger_segment_rotation_and_snapshot_compaction(tmp_path, monkeypatch):
    monkeypatch.setenv("TRC_HA_SEGMENT_RECORDS", "10")
    monkeypatch.setenv("TRC_HA_SNAPSHOT_EVERY", "0")  # manual snapshots
    ledger = JobLedger.open(tmp_path)
    ledger.append_job_started("j1")
    for frame in range(25):
        ledger.append_unit_finished("j1", frame)
    assert len(list(tmp_path.glob("segment-*.jsonl"))) >= 3
    ledger.snapshot()
    # Every pre-snapshot segment is pruned; state fully in snapshot.json.
    assert list(tmp_path.glob("segment-*.jsonl")) == []
    ledger.append_unit_finished("j1", 25)
    ledger.append_job_finished("j1")
    ledger.close()
    replay = JobLedger.replay_directory(tmp_path)
    assert replay.finished_units("j1") == {(f, None) for f in range(26)}
    assert replay.job("j1").status == "finished"


def test_ledger_job_name_reuse_starts_fresh_generation(tmp_path):
    ledger = JobLedger.open(tmp_path)
    ledger.append_job_started("reuse")
    ledger.append_unit_finished("reuse", 1)
    ledger.append_job_finished("reuse")
    # Same name, NEW submission: the old generation's units must not
    # credit the new job.
    ledger.append_job_started("reuse")
    ledger.close()
    replay = JobLedger.replay_directory(tmp_path)
    assert replay.finished_units("reuse") == set()
    assert replay.job("reuse").status == "started"


# ---------------------------------------------------------------------------
# Replay -> state application + unified resume


def _replay_with(job_name, units, status="started"):
    replay = LedgerReplay(epoch=2)
    replay.apply({"v": 1, "seq": 1, "type": "job_started", "job": job_name})
    seq = 1
    for frame, tile in units:
        seq += 1
        replay.apply(
            {
                "v": 1,
                "seq": seq,
                "type": "unit_finished",
                "job": job_name,
                "frame": frame,
                "tile": tile,
            }
        )
    if status == "finished":
        replay.apply(
            {"v": 1, "seq": seq + 1, "type": "job_finished", "job": job_name}
        )
    return replay


def test_apply_ledger_marks_units_and_skips_unknown():
    job = make_job(frames=4)
    state = ClusterManagerState(job)
    replay = _replay_with("ha-job", [(1, None), (3, None), (77, None)])
    replayed, needs_stitch = apply_ledger_to_state(state, replay)
    assert replayed == 2  # frame 77 is not in the job
    assert needs_stitch == []
    assert state.frames[WorkUnit(1)].status is FrameStatus.FINISHED
    assert state.frames[WorkUnit(3)].status is FrameStatus.FINISHED
    assert state.finished_count() == 2


def test_apply_ledger_closed_generation_needs_include_closed():
    job = make_job(frames=4)
    replay = _replay_with("ha-job", [(1, None)], status="finished")
    state = ClusterManagerState(job)
    assert apply_ledger_to_state(state, replay) == (0, [])
    state = ClusterManagerState(job)
    assert apply_ledger_to_state(state, replay, include_closed=True)[0] == 1


def test_apply_ledger_tiled_restitch_detection():
    """All tiles of a frame replayed finished but no assembly record:
    the frame needs a re-stitch on the standby."""
    job = make_job(frames=2, tile_grid=(1, 2))
    state = ClusterManagerState(job)
    replay = _replay_with("ha-job", [(1, 0), (1, 1), (2, 0)])
    replay.apply(
        {"v": 1, "seq": 50, "type": "frame_assembled", "job": "ha-job", "frame": 1}
    )
    # Frame 1 fully tiled + assembled record; re-apply to a fresh state
    # where frame 1 would otherwise need a stitch.
    replayed, needs_stitch = apply_ledger_to_state(state, replay)
    assert replayed == 3
    assert needs_stitch == []  # frame 1 assembled, frame 2 incomplete
    assert state.frames_assembled == 1

    replay2 = _replay_with("ha-job", [(2, 0), (2, 1)])
    state2 = ClusterManagerState(job)
    replayed2, needs_stitch2 = apply_ledger_to_state(state2, replay2)
    assert replayed2 == 2
    assert needs_stitch2 == [2]  # crash hit between last tile and stitch


def test_resume_prefers_ledger_over_scan(tmp_path):
    """Satellite: a resumed job never re-renders units the ledger
    recorded as finished — the ledger wins over the output scan."""
    job_dict = make_job(frames=4).to_dict()
    job_dict["output_directory_path"] = str(tmp_path / "out")
    job = BlenderJob.from_dict(job_dict)
    # The scan would claim frames 1-2 (files on disk, one of them a lie
    # left by a half-written run the ledger knows nothing about)...
    out = tmp_path / "out"
    out.mkdir()
    (out / "rendered-00001.png").write_bytes(b"x" * 10)
    (out / "rendered-00002.png").write_bytes(b"x" * 10)
    # ...but the ledger only recorded frame 3.
    replay = _replay_with("ha-job", [(3, None)])
    state = ClusterManagerState(job)
    restored = apply_resume(state, job, ledger_replay=replay)
    assert restored == 1
    assert state.frames[WorkUnit(3)].status is FrameStatus.FINISHED
    assert state.frames[WorkUnit(1)].status is FrameStatus.PENDING

    # No ledger record of the job -> the scan fallback applies.
    state = ClusterManagerState(job)
    restored = apply_resume(state, job, ledger_replay=LedgerReplay(epoch=1))
    assert restored == 2
    assert state.frames[WorkUnit(1)].status is FrameStatus.FINISHED
    assert state.frames[WorkUnit(3)].status is FrameStatus.PENDING


# ---------------------------------------------------------------------------
# Epoch fencing: wire form + both refusal ends


def test_epoch_piggyback_roundtrip_and_byte_identity():
    plain = pm.MasterHandshakeRequest("1.0.0")
    assert "epoch" not in pm.encode_message(plain)
    stamped = pm.decode_message(
        pm.encode_message(pm.MasterHandshakeRequest("1.0.0", epoch=4))
    )
    assert stamped.epoch == 4
    add = pm.MasterFrameQueueAddRequest.new(make_job(), 1, epoch=7)
    assert pm.decode_message(pm.encode_message(add)).epoch == 7
    done = pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 1, epoch=7)
    assert pm.decode_message(pm.encode_message(done)).epoch == 7
    # Epoch-less events stay byte-identical to the reference shape.
    legacy = pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 1)
    assert "epoch" not in pm.encode_message(legacy)
    with pytest.raises(ValueError):
        pm.MasterHandshakeRequest.from_payload(
            {"server_version": "1", "epoch": "three"}
        )


def _bare_handle(state, epoch):
    from tpu_render_cluster.master.queue_mirror import WorkerQueueMirror
    from tpu_render_cluster.master.worker_handle import WorkerHandle
    from tpu_render_cluster.utils.logging import WorkerLogger

    handle = WorkerHandle.__new__(WorkerHandle)
    handle.worker_id = 0xF0
    handle.state = state
    handle._state_resolver = None
    handle.is_dead = False
    handle.metrics = MetricsRegistry()
    handle.span_tracer = None
    handle.drained = False
    handle.epoch = epoch
    handle.queue = WorkerQueueMirror()
    handle._rendering_started_at = {}
    handle._completion_observations = []
    handle._on_frame_complete = None
    handle._on_unit_latency = None
    handle.logger = WorkerLogger(
        logging.getLogger("test.ha"), "000000f0", "test"
    )
    return handle


def test_master_refuses_stale_epoch_results():
    """A finished event echoing a PREVIOUS incarnation's epoch is counted
    and refused before it can touch the ok/duplicate ledger."""
    from tpu_render_cluster.chaos.invariants import counter_total

    state = ClusterManagerState(make_job(frames=4))
    handle = _bare_handle(state, epoch=2)
    stale = pm.WorkerFrameQueueItemFinishedEvent.new_ok("ha-job", 1, epoch=1)
    handle._apply_finished_event(stale)
    assert state.frames[WorkUnit(1)].status is FrameStatus.PENDING
    assert state.ledger["ok_results"] == 0
    assert state.ledger["stale_epoch_results"] == 1
    snapshot = handle.metrics.snapshot()
    assert counter_total(snapshot, "master_stale_epoch_events_total") == 1
    # The fence also stops rendering events.
    handle._apply_rendering_event(
        pm.WorkerFrameQueueItemRenderingEvent("ha-job", 2, epoch=1)
    )
    assert state.frames[WorkUnit(2)].status is FrameStatus.PENDING
    assert state.ledger["stale_epoch_results"] == 2
    # Same-epoch traffic is applied normally (the fence is inert).
    state.mark_frame_as_queued(WorkUnit(1), handle.worker_id, 0.0)
    handle._apply_finished_event(
        pm.WorkerFrameQueueItemFinishedEvent.new_ok("ha-job", 1, epoch=2)
    )
    assert state.frames[WorkUnit(1)].status is FrameStatus.FINISHED
    assert state.ledger["ok_results"] == 1


def test_worker_queue_reset_session_drops_only_queued():
    from tpu_render_cluster.worker.queue import FrameState, WorkerAutomaticQueue

    queue = WorkerAutomaticQueue.__new__(WorkerAutomaticQueue)
    queue._frames = []
    queue._finished_indices = {("ha-job", 1, None)}
    queue._session_generation = 0
    queue._draining = False

    class _Event:
        def set(self):
            pass

    queue._work_available = _Event()
    job = make_job(frames=8)
    for frame in (2, 3, 4):
        queue._frames.append(
            type(
                "F",
                (),
                {"job": job, "frame_index": frame, "state": FrameState.QUEUED,
                 "tile": None},
            )()
        )
    queue._frames[0].state = FrameState.RENDERING
    dropped = queue.reset_session()
    assert dropped == 2
    assert [f.frame_index for f in queue._frames] == [2]
    assert queue._finished_indices == set()
    # The generation bump fences the mid-render frame (queued under
    # session 0) out of the finished index when it later completes —
    # otherwise a remove RPC for the NEW master's re-assignment of that
    # unit would falsely answer already-finished.
    assert queue._session_generation == 1
    assert queue._frames[0].state is FrameState.RENDERING


def test_new_ha_metric_names_pass_the_naming_lint():
    for name, kind, labels in [
        ("ha_ledger_appends_total", "counter", ("type",)),
        ("ha_ledger_snapshots_total", "counter", ()),
        ("ha_ledger_replayed_units_total", "counter", ()),
        ("ha_router_requests_total", "counter", ("op", "shard")),
        ("ha_router_jobs_routed_total", "counter", ("shard",)),
        ("master_stale_epoch_events_total", "counter", ()),
        ("worker_stale_epoch_requests_total", "counter", ()),
        ("worker_session_reannounces_total", "counter", ()),
        ("ha_replication_followers_units", "gauge", ()),
        ("ha_replication_behind_units", "gauge", ()),
        ("ha_replication_lag_units", "gauge", ("follower",)),
        ("ha_replication_lag_seconds", "histogram", ()),
        ("ha_replication_records_sent_total", "counter", ("follower",)),
        ("ha_replication_records_applied_total", "counter", ()),
        ("ha_replication_reconnects_total", "counter", ()),
        ("ha_replication_gaps_total", "counter", ()),
        ("ha_replication_torn_tails_total", "counter", ()),
        ("ha_replication_refused_total", "counter", ("end",)),
        ("ha_replication_snapshots_sent_total", "counter", ()),
        ("ha_failover_mttr_seconds", "gauge", ()),
        ("ha_router_promotions_total", "counter", ("shard",)),
        ("ha_router_scrapes_total", "counter", ("path", "shard")),
        ("ha_router_scrape_failures_total", "counter", ("shard",)),
        ("ha_router_shard_load_units", "gauge", ("shard",)),
        ("ha_router_rebalance_moves_total", "counter", ("source", "target")),
        ("worker_migrations_total", "counter", ()),
        ("master_worker_migrations_total", "counter", ()),
        ("master_worker_migrate_requests_total", "counter", ()),
    ]:
        assert lint_metric(name, kind, labels) == [], name


# ---------------------------------------------------------------------------
# Failover plan vocabulary


def test_failover_plan_is_seeded_and_master_targeted():
    a = FaultPlan.generate_failover(ACCEPTANCE_SEED, 3)
    b = FaultPlan.generate_failover(ACCEPTANCE_SEED, 3)
    assert a.fingerprint() == b.fingerprint()
    kinds = a.kinds()
    assert KIND_MASTER_KILL in kinds and KIND_MASTER_PARTITION in kinds
    assert all(e.target == MASTER_TARGET for e in a.master_events())
    assert a.expected_evictions() == 0  # every worker survives to re-adopt
    # Pre-HA seeds keep bit-identical schedules (the new kinds draw last).
    legacy = FaultPlan.generate(ACCEPTANCE_SEED, 3)
    assert not legacy.master_events()


def test_replication_chaos_kinds_draw_last_and_scenarios_are_seeded():
    """The three replication kinds draw LAST from the plan RNG: adding
    them to a seeded plan leaves every pre-existing event bit-identical,
    so recorded legacy seeds keep their schedules."""
    base = FaultPlan.generate(ACCEPTANCE_SEED, 3, master_kills=1)
    extended = FaultPlan.generate(
        ACCEPTANCE_SEED,
        3,
        master_kills=1,
        replication_partitions=1,
        router_kills=1,
        follower_lags=1,
    )
    assert not base.replication_events()
    assert len(extended.replication_events()) == 3
    assert [
        e for e in extended.events if e.kind not in REPLICATION_KINDS
    ] == list(base.events)

    rep = FaultPlan.generate_replicated_failover(ACCEPTANCE_SEED)
    assert (
        rep.fingerprint()
        == FaultPlan.generate_replicated_failover(ACCEPTANCE_SEED).fingerprint()
    )
    kinds = rep.kinds()
    assert KIND_MASTER_KILL in kinds
    assert KIND_REPLICATION_PARTITION in kinds and KIND_FOLLOWER_LAG in kinds
    assert rep.expected_evictions() == 0  # every worker survives

    shard_kill = FaultPlan.generate_shard_kill(ACCEPTANCE_SEED)
    assert KIND_MASTER_KILL in shard_kill.kinds()
    assert KIND_ROUTER_KILL in shard_kill.kinds()
    assert shard_kill.expected_evictions() == 0


# ---------------------------------------------------------------------------
# Seeded failover acceptance (the tier-1 e2e)


@pytest.fixture(scope="module")
def failover_run(tmp_path_factory):
    plan = FaultPlan.generate_failover(ACCEPTANCE_SEED, 3)
    results = tmp_path_factory.mktemp("failover-artifacts")
    report = run_chaos_failover_job(
        plan,
        frames=48,
        results_directory=results,
        ledger_directory=tmp_path_factory.mktemp("failover-ledger"),
        timeout=120.0,
    )
    return report


def test_failover_acceptance_invariants(failover_run):
    """Master killed mid-job; the standby replays the ledger, re-adopts
    the live workers, and the job completes with the cross-incarnation
    exactly-once audit green and zero ghost mirror entries."""
    report = failover_run
    assert report.ok, report.violations
    failover = report.stats["failover"]
    assert failover["standby_epoch"] == failover["primary_epoch"] + 1
    assert "kill_at" in failover  # the kill actually fired mid-run
    assert failover["mttr_seconds"] > 0.0
    ledger = report.stats["ledger"]
    assert (
        failover["replayed_units"]
        + ledger["ok_results"]
        - ledger["duplicate_results"]
        == report.stats["frames_total"]
    )
    assert ledger["evictions"] == 0 and ledger["drains"] == 0


def test_failover_acceptance_artifacts_valid(failover_run):
    """The failover run's exported timelines hold every structural
    invariant — no dangling flows even though a master died mid-chain
    (scripts/validate_trace.py runs the same checks)."""
    report = failover_run
    assert report.artifacts
    for path in report.artifacts.values():
        if path.endswith("trace-events.json"):
            assert validate_trace_file(path) == []
    metrics_path = Path(report.artifacts["metrics"])
    snapshot = json.loads(metrics_path.read_text())["metrics"]
    assert "ha_ledger_appends_total" in snapshot
    assert "ha_ledger_replayed_units_total" in snapshot


# ---------------------------------------------------------------------------
# Scheduler + ledger: replay at admission


def test_job_manager_replays_ledger_at_admission(tmp_path):
    """A restarted scheduler re-admits a job and only renders what the
    ledger has not recorded: the predecessor's finished units are
    restored, the remainder dispatched."""
    job = make_job(name="ha-sched", frames=6)
    seed_ledger = JobLedger.open(tmp_path)
    seed_ledger.append_job_started(
        "ha-sched", spec=job.to_dict(), job_id="job-0001"
    )
    for frame in (1, 2, 3):
        seed_ledger.append_unit_finished("ha-sched", frame)
    seed_ledger.close()

    ledger = JobLedger.open(tmp_path)
    _worker_traces, job_ids, manager, _workers = _run_ledgered_multi_job(
        job, ledger
    )
    run = manager._runs[job_ids[0]]
    assert run.status == "finished"
    assert run.state.finished_count() == 6
    # Only the 3 unreplayed frames crossed the wire as results.
    assert run.state.ledger["ok_results"] == 3
    replay = JobLedger.replay_directory(tmp_path)
    assert replay.job("ha-sched").status == "finished"
    assert replay.finished_units("ha-sched") == {
        (f, None) for f in range(1, 7)
    }


def _run_ledgered_multi_job(job, ledger):
    from tpu_render_cluster.harness.local import _run_multi_job
    from tpu_render_cluster.sched.manager import JobManager
    from tpu_render_cluster.sched.models import JobSpec
    from tpu_render_cluster.worker.backends.mock import MockBackend

    return asyncio.run(
        asyncio.wait_for(
            _run_multi_job(
                [JobSpec(job=job)],
                [MockBackend(render_seconds=0.01)],
                manager_factory=lambda: JobManager(
                    "127.0.0.1", 0, metrics=MetricsRegistry(), ledger=ledger
                ),
            ),
            60.0,
        )
    )


# ---------------------------------------------------------------------------
# Shard router


def test_shard_hashing_is_stable_and_routed_ids_parse():
    assert shard_for_job_name("alpha", 2) == shard_for_job_name("alpha", 2)
    assert {shard_for_job_name(f"job-{i}", 4) for i in range(64)} == {0, 1, 2, 3}
    assert split_routed_job_id("s2/job-0007") == (2, "job-0007")
    assert split_routed_job_id("job-0007") is None
    assert split_routed_job_id("sX/job-0007") is None


def test_shard_router_end_to_end_two_shards():
    """Submit through the router over real sockets: jobs hash across two
    live JobManager shards (each owning its own worker), routed status /
    global fan-out / drain all answer, and every job finishes."""
    from tpu_render_cluster.sched.control import ControlServer, control_request
    from tpu_render_cluster.sched.manager import JobManager
    from tpu_render_cluster.worker.backends.mock import MockBackend
    from tpu_render_cluster.worker.runtime import Worker

    async def scenario():
        shards, serves, controls, wtasks = [], [], [], []
        for _ in range(2):
            manager = JobManager("127.0.0.1", 0, metrics=MetricsRegistry())
            serve_task = asyncio.create_task(manager.serve())
            while manager._server is None:
                await asyncio.sleep(0.01)
            control = ControlServer(manager, "127.0.0.1", 0)
            await control.start()
            worker = Worker(
                "127.0.0.1",
                manager.port,
                MockBackend(render_seconds=0.01),
                metrics=MetricsRegistry(),
            )
            wtasks.append(
                asyncio.create_task(worker.connect_and_run_to_job_completion())
            )
            shards.append(manager)
            serves.append(serve_task)
            controls.append(control)
        router = ShardRouter(
            [("127.0.0.1", c.port) for c in controls],
            metrics=MetricsRegistry(),
        )
        server = ShardRouterServer(router)
        await server.start()

        async def rr(request):
            return await control_request("127.0.0.1", server.port, request)

        names = ["alpha", "bravo", "charlie", "delta"]
        job_ids = []
        for name in names:
            response = await rr(
                {"op": "submit", "spec": {"job": make_job(name, frames=4).to_dict()}}
            )
            assert response["ok"], response
            expected_shard = router.shard_for(name)
            assert response["job_id"].startswith(f"s{expected_shard}/")
            job_ids.append(response["job_id"])
        # Routed single-job status reaches the owning shard.
        status = await rr({"op": "status", "job_id": job_ids[0]})
        assert status["ok"] and status["job"]["job_name"] == names[0]
        # Unprefixed ids are rejected loudly, not misrouted.
        bad = await rr({"op": "status", "job_id": "job-0001"})
        assert not bad["ok"] and "shard-routed" in bad["error"]
        # Global status fans out and aggregates per shard.
        global_status = await rr({"op": "status"})
        assert global_status["ok"]
        assert set(global_status["shards"]) == {"0", "1"}
        drained = await rr({"op": "drain"})
        assert drained["ok"]
        await asyncio.gather(*serves)
        for manager in shards:
            for run in manager._runs.values():
                assert run.status == "finished"
        # Both shards got work (the four names split under crc32).
        assert all(len(m._runs) >= 1 for m in shards)
        await server.stop()
        for control in controls:
            await control.stop()
        await asyncio.gather(*wtasks, return_exceptions=True)

    asyncio.run(asyncio.wait_for(scenario(), 90.0))


# ---------------------------------------------------------------------------
# Ledger streaming replication (ha/replicate.py)


async def _until(predicate, timeout=15.0):
    async def _poll():
        while not predicate():
            await asyncio.sleep(0.01)

    await asyncio.wait_for(_poll(), timeout)


def test_replication_backlog_live_tail_and_promotion(tmp_path):
    """A follower attaches (backlog re-fetch over TCP), tails live
    commits, and promotes to a ledger whose epoch out-fences every epoch
    the primary ever streamed — no shared filesystem anywhere."""
    primary_dir = tmp_path / "primary"
    replica_dir = tmp_path / "replica"

    async def scenario():
        ledger = JobLedger.open(primary_dir)
        assert ledger.epoch == 1
        ledger.append_job_started("rep", spec={"x": 1}, job_id="job-0001")
        ledger.append_unit_finished("rep", 1)
        registry = MetricsRegistry()
        server = ReplicationServer(ledger, metrics=registry)
        await server.start()
        follower = LedgerFollower(
            replica_dir,
            "127.0.0.1",
            server.port,
            metrics=MetricsRegistry(),
            follower_id="t-backlog",
        )
        follower.start()
        await _until(lambda: follower.last_seq >= 2)  # the backlog
        ledger.append_unit_finished("rep", 2)  # the live tail
        await _until(lambda: follower.last_seq >= 3)
        assert follower.records_applied == 3
        assert follower.epoch == 1 and not follower.fenced
        snapshot = registry.snapshot()
        assert counter_total(snapshot, "ha_replication_records_sent_total") == 3
        promoted = await follower.promote()
        try:
            assert promoted.epoch == 2  # strictly above the primary's 1
            assert promoted.replay.finished_units("rep") == {
                (1, None),
                (2, None),
            }
            assert promoted.replay.job("rep").job_id == "job-0001"
        finally:
            promoted.close()
            await server.stop()
            ledger.close()

    asyncio.run(asyncio.wait_for(scenario(), 30.0))


def test_replication_ships_snapshot_when_attach_predates_compaction(
    tmp_path, monkeypatch
):
    """A follower attaching below the primary's compaction floor gets the
    snapshot plus the post-snapshot records — and its replica replays to
    the same state the primary holds."""
    monkeypatch.setenv("TRC_HA_SNAPSHOT_EVERY", "0")
    primary_dir = tmp_path / "primary"
    replica_dir = tmp_path / "replica"

    async def scenario():
        ledger = JobLedger.open(primary_dir)
        ledger.append_job_started("snap")
        for frame in range(8):
            ledger.append_unit_finished("snap", frame)
        ledger.snapshot()  # prunes every segment behind the floor
        ledger.append_unit_finished("snap", 8)
        registry = MetricsRegistry()
        server = ReplicationServer(ledger, metrics=registry)
        await server.start()
        follower = LedgerFollower(
            replica_dir,
            "127.0.0.1",
            server.port,
            metrics=MetricsRegistry(),
            follower_id="t-snap",
        )
        follower.start()
        await _until(lambda: follower.last_seq >= ledger.replay.last_seq)
        await follower.stop()
        await server.stop()
        ledger.close()
        assert (replica_dir / "snapshot.json").exists()
        snapshot = registry.snapshot()
        assert (
            counter_total(snapshot, "ha_replication_snapshots_sent_total") == 1
        )
        replay = JobLedger.replay_directory(replica_dir)
        assert replay.finished_units("snap") == {(f, None) for f in range(9)}

    asyncio.run(asyncio.wait_for(scenario(), 30.0))


def test_replication_torn_midstream_record_refetched_never_applied(
    tmp_path, monkeypatch
):
    """The primary dies mid-record: the follower discards the torn line
    WITHOUT applying it, re-attaches from its last contiguous record, and
    re-fetches — the replica replays clean, exactly once."""
    monkeypatch.setenv("TRC_HA_REPL_RETRY_SECONDS", "0.05")
    records = [
        {"v": 1, "seq": 1, "type": "job_started", "job": "torn"},
        {"v": 1, "seq": 2, "type": "unit_finished", "job": "torn", "frame": 1},
        {"v": 1, "seq": 3, "type": "unit_finished", "job": "torn", "frame": 2},
    ]
    attach_positions = []

    async def scenario():
        async def fake_primary(reader, writer):
            line = await reader.readline()
            request = pm.decode_message(line)
            attach_positions.append(request.last_seq)
            writer.write(
                _encode_line(
                    pm.ReplicationAttachResponse(
                        request.message_request_id, epoch=1, primary_seq=3
                    )
                )
            )
            if len(attach_positions) == 1:
                # Record 1 lands whole; record 2 is severed mid-line.
                writer.write(
                    _encode_line(pm.ReplicationRecordEvent(1, records[0]))
                )
                torn = _encode_line(pm.ReplicationRecordEvent(2, records[1]))
                writer.write(torn[: len(torn) // 2])
                await writer.drain()
                writer.close()
                return
            for record in records:
                if record["seq"] > request.last_seq:
                    writer.write(
                        _encode_line(
                            pm.ReplicationRecordEvent(record["seq"], record)
                        )
                    )
            await writer.drain()
            await reader.read()  # hold the stream open until the follower stops
            writer.close()  # 3.12: Server.wait_closed() waits for open connections

        fake = await asyncio.start_server(fake_primary, "127.0.0.1", 0)
        port = fake.sockets[0].getsockname()[1]
        registry = MetricsRegistry()
        follower = LedgerFollower(
            tmp_path, "127.0.0.1", port, metrics=registry, follower_id="t-torn"
        )
        follower.start()
        await _until(lambda: follower.last_seq >= 3)
        await follower.stop()
        fake.close()
        await fake.wait_closed()
        # Re-attached exactly from the last contiguous record, not 0.
        assert attach_positions == [0, 1]
        snapshot = registry.snapshot()
        assert counter_total(snapshot, "ha_replication_torn_tails_total") >= 1
        assert counter_total(snapshot, "ha_replication_reconnects_total") >= 1
        # The torn record was never half-applied: the replica replays to
        # exactly the three records, each once.
        assert follower.records_applied == 3
        replay = JobLedger.replay_directory(tmp_path)
        assert not replay.torn_tail
        assert replay.finished_units("torn") == {(1, None), (2, None)}

    asyncio.run(asyncio.wait_for(scenario(), 30.0))


def test_promotion_race_revived_primary_refused_both_ends(
    tmp_path, monkeypatch
):
    """A follower promotes while the old primary revives: the stale
    primary refuses the newer-epoch follower (it learns it is deposed),
    and a follower refuses a primary streaming an older epoch than its
    replica has durably observed — fenced at BOTH ends of the wire."""
    monkeypatch.setenv("TRC_HA_REPL_RETRY_SECONDS", "0.05")
    primary_dir = tmp_path / "primary"
    replica_dir = tmp_path / "replica"

    async def scenario():
        ledger = JobLedger.open(primary_dir)  # epoch 1
        ledger.append_job_started("race")
        primary_registry = MetricsRegistry()
        server = ReplicationServer(ledger, metrics=primary_registry)
        await server.start()
        follower = LedgerFollower(
            replica_dir,
            "127.0.0.1",
            server.port,
            metrics=MetricsRegistry(),
            follower_id="race-1",
        )
        follower.start()
        await _until(lambda: follower.last_seq >= 1)
        promoted = await follower.promote()  # the race winner: epoch 2
        assert promoted.epoch == 2
        promoted.close()

        # Primary end: the revived epoch-1 primary must refuse a replica
        # that has durably seen epoch 2 — never stream a stale timeline.
        stale = LedgerFollower(
            replica_dir,
            "127.0.0.1",
            server.port,
            metrics=MetricsRegistry(),
            follower_id="race-2",
        )
        assert stale.epoch == 2  # from the replica's EPOCH file
        stale.start()
        await _until(lambda: stale.fenced)
        await stale.stop()
        assert stale.last_seq == 1  # nothing from the stale stream applied
        assert (
            counter_total(
                primary_registry.snapshot(), "ha_replication_refused_total"
            )
            == 1
        )
        await server.stop()
        ledger.close()

        # Follower end: a primary that STREAMS an older epoch than the
        # replica observed is refused by the follower (the mirror-image
        # fence, for a primary that skips the request-side check).
        async def stale_primary(reader, writer):
            line = await reader.readline()
            request = pm.decode_message(line)
            writer.write(
                _encode_line(
                    pm.ReplicationAttachResponse(
                        request.message_request_id, epoch=1, primary_seq=9
                    )
                )
            )
            await writer.drain()
            await reader.read()
            writer.close()  # 3.12: Server.wait_closed() waits for open connections

        fake = await asyncio.start_server(stale_primary, "127.0.0.1", 0)
        fake_port = fake.sockets[0].getsockname()[1]
        follower_registry = MetricsRegistry()
        refuser = LedgerFollower(
            replica_dir,
            "127.0.0.1",
            fake_port,
            metrics=follower_registry,
            follower_id="race-3",
        )
        refuser.start()
        await _until(lambda: refuser.fenced)
        await refuser.stop()
        fake.close()
        await fake.wait_closed()
        assert refuser.last_seq == 1
        assert (
            counter_total(
                follower_registry.snapshot(), "ha_replication_refused_total"
            )
            == 1
        )

    asyncio.run(asyncio.wait_for(scenario(), 30.0))


# ---------------------------------------------------------------------------
# Rebalance planner: threshold / hysteresis / cooldown (pure, no sockets)


def test_rebalance_planner_hysteresis_prevents_flapping():
    planner = RebalancePlanner(
        threshold=2.0, hysteresis_ticks=3, cooldown_seconds=30.0, max_moves=2
    )
    hot = ShardLoad(shard=0, queue_depth=40, in_flight_cost_seconds=None, workers=4)
    cold = ShardLoad(shard=1, queue_depth=2, in_flight_cost_seconds=None, workers=4)
    even = ShardLoad(shard=0, queue_depth=2, in_flight_cost_seconds=None, workers=4)
    # A short spike never moves anyone...
    assert planner.observe([hot, cold], 1000.0) is None
    assert planner.observe([hot, cold], 1001.0) is None
    # ...a balanced tick resets the streak...
    assert planner.observe([even, cold], 1002.0) is None
    assert planner.observe([hot, cold], 1003.0) is None
    assert planner.observe([hot, cold], 1004.0) is None
    # ...and only a PERSISTENT imbalance fires.
    move = planner.observe([hot, cold], 1005.0)
    assert isinstance(move, Move)
    assert (move.source, move.target, move.count) == (0, 1, 1)
    # Cooldown: the imbalance persists, but no second move inside it —
    # the migrated workers need time to land before the next decision.
    for tick in range(6):
        assert planner.observe([hot, cold], 1006.0 + tick) is None
    # After the cooldown, the still-persistent imbalance may fire again.
    assert planner.observe([hot, cold], 1035.0) is not None


def test_rebalance_planner_excludes_dead_and_undrainable_shards():
    planner = RebalancePlanner(
        threshold=1.5, hysteresis_ticks=1, cooldown_seconds=0.0
    )
    hot = ShardLoad(
        shard=0, queue_depth=100, in_flight_cost_seconds=None, workers=4
    )
    # A dead shard is never a migration target — its workers re-home
    # through the router, not via ops a dead control plane cannot serve.
    assert planner.observe([hot, ShardLoad.dead(1)], 0.0) is None
    # A single-worker hot shard is never drained below one worker.
    lone = ShardLoad(
        shard=0, queue_depth=100, in_flight_cost_seconds=None, workers=1
    )
    idle = ShardLoad(shard=1, queue_depth=0, in_flight_cost_seconds=None, workers=1)
    assert planner.observe([lone, idle], 1.0) is None
    # Cost-based ranking only when EVERY live shard reports cost.
    costed = ShardLoad(
        shard=0, queue_depth=1, in_flight_cost_seconds=90.0, workers=2
    )
    uncosted = ShardLoad(
        shard=1, queue_depth=1, in_flight_cost_seconds=None, workers=2
    )
    assert planner.observe([costed, uncosted], 2.0) is None  # unit tie
    both = ShardLoad(
        shard=1, queue_depth=1, in_flight_cost_seconds=1.0, workers=2
    )
    move = planner.observe([costed, both], 3.0)
    assert move is not None and (move.source, move.target) == (0, 1)


# ---------------------------------------------------------------------------
# Router degradation + worker migration over real sockets


def test_router_fanout_degrades_dead_shard_to_absence():
    """A dead shard is ABSENT from the router's fan-out answers (and
    counted in ha_router_scrape_failures_total), never surfaced as a
    connection error poisoning the whole response."""
    import socket

    from tpu_render_cluster.sched.control import ControlServer, control_request
    from tpu_render_cluster.sched.manager import JobManager

    async def scenario():
        manager = JobManager("127.0.0.1", 0, metrics=MetricsRegistry())
        serve_task = asyncio.create_task(manager.serve())
        while manager._server is None:
            await asyncio.sleep(0.01)
        control = ControlServer(manager, "127.0.0.1", 0)
        await control.start()
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        registry = MetricsRegistry()
        router = ShardRouter(
            [("127.0.0.1", control.port), ("127.0.0.1", dead_port)],
            timeout=2.0,
            metrics=registry,
        )
        server = ShardRouterServer(router)
        await server.start()

        async def rr(request):
            return await control_request("127.0.0.1", server.port, request)

        for op in ("status", "alerts", "ping"):
            response = await rr({"op": op})
            assert response["ok"], response
            assert set(response["shards"]) == {"0"}
            assert response["unreachable"] == [1]
        snapshot = registry.snapshot()
        assert counter_total(snapshot, "ha_router_scrape_failures_total") >= 3
        drained = await rr({"op": "drain"})
        assert drained["ok"] and drained["unreachable"] == [1]
        await server.stop()
        await control.stop()
        serve_task.cancel()
        await asyncio.gather(serve_task, return_exceptions=True)

    asyncio.run(asyncio.wait_for(scenario(), 60.0))


def test_migrate_workers_rehomes_worker_to_target_shard():
    """The migrate_workers control op sheds a worker shard A -> shard B
    via a graceful migrate goodbye: the worker departs WITHOUT counting
    as a drain, re-announces at B, and renders B's job to completion."""
    from tpu_render_cluster.sched.control import ControlServer, control_request
    from tpu_render_cluster.sched.manager import JobManager
    from tpu_render_cluster.worker.backends.mock import MockBackend
    from tpu_render_cluster.worker.runtime import Worker

    async def scenario():
        managers, serves, controls = [], [], []
        for _ in range(2):
            manager = JobManager("127.0.0.1", 0, metrics=MetricsRegistry())
            serve_task = asyncio.create_task(manager.serve())
            while manager._server is None:
                await asyncio.sleep(0.01)
            control = ControlServer(manager, "127.0.0.1", 0)
            await control.start()
            managers.append(manager)
            serves.append(serve_task)
            controls.append(control)
        submitted = await control_request(
            "127.0.0.1",
            controls[1].port,
            {
                "op": "submit",
                "spec": {"job": make_job("migrate-target", frames=4).to_dict()},
            },
        )
        assert submitted["ok"], submitted

        worker_registry = MetricsRegistry()
        worker = Worker(
            "127.0.0.1",
            managers[0].port,
            MockBackend(render_seconds=0.01),
            metrics=worker_registry,
        )

        async def no_route():
            return None

        worker_task = asyncio.create_task(worker.connect_and_serve(no_route))
        await _until(lambda: len(managers[0].workers) == 1)
        moved = await control_request(
            "127.0.0.1",
            controls[0].port,
            {
                "op": "migrate_workers",
                "host": "127.0.0.1",
                "port": managers[1].port,
                "reason": "test rebalance",
            },
        )
        assert moved["ok"] and moved["migrating"] == 1
        drained = await control_request(
            "127.0.0.1", controls[1].port, {"op": "drain"}
        )
        assert drained["ok"]
        await asyncio.wait_for(serves[1], 60.0)
        run = next(iter(managers[1]._runs.values()))
        assert run.status == "finished"
        assert run.state.finished_count() == 4
        # The goodbye was a MIGRATE, not a drain — counted apart so the
        # chaos audits' drain ledger stays exact.
        assert (
            counter_total(worker_registry.snapshot(), "worker_migrations_total")
            == 1
        )
        source_snapshot = managers[0].metrics.snapshot()
        assert (
            counter_total(source_snapshot, "master_worker_migrations_total") == 1
        )
        assert (
            counter_total(
                source_snapshot, "master_worker_migrate_requests_total"
            )
            == 1
        )
        assert counter_total(source_snapshot, "master_worker_drains_total") == 0
        await asyncio.gather(worker_task, return_exceptions=True)
        serves[0].cancel()
        await asyncio.gather(serves[0], return_exceptions=True)
        for control in controls:
            await control.stop()

    asyncio.run(asyncio.wait_for(scenario(), 90.0))


# ---------------------------------------------------------------------------
# Seeded cross-host acceptance runs (replication + shard death)


def test_replicated_failover_acceptance(tmp_path):
    """Cross-host failover under chaos: the stream is severed and lagged,
    the primary killed — the router's monitor promotes the follower
    (epoch-fenced), and the promoted replica finishes the job with the
    exactly-once audit green. NO shared filesystem between the hosts."""
    plan = FaultPlan.generate_replicated_failover(7, workers=3)
    report = run_chaos_replicated_failover(
        plan,
        frames=24,
        primary_directory=tmp_path / "primary",
        replica_directory=tmp_path / "replica",
        timeout=120.0,
    )
    assert report.ok, report.violations
    failover = report.stats["failover"]
    assert len(failover["promotions"]) == 1
    assert failover["standby_epoch"] > failover["primary_epoch"]
    assert failover["follower"]["records_applied"] > 0
    assert failover["mttr_seconds"] > 0.0
    ledger = report.stats["ledger"]
    assert (
        failover["replayed_units"]
        + ledger["ok_results"]
        - ledger["duplicate_results"]
        == report.stats["frames_total"]
    )


def test_shard_kill_workers_rehome_to_survivor(tmp_path):
    """One of two router-fronted shards dies mid-backlog (master AND
    control endpoint — a whole host), the router bounces once: every
    orphaned worker re-homes through route_worker, the survivor finishes
    the full backlog exactly once, and the router's fan-outs degrade the
    dead shard to absence."""
    plan = FaultPlan.generate_shard_kill(11, workers=4)
    report = run_chaos_shard_kill(plan, jobs=2, frames=16, timeout=180.0)
    assert report.ok, report.violations
    shard_kill = report.stats["shard_kill"]
    assert shard_kill["survivor_workers"] == plan.workers
    assert shard_kill["drain_ok"]
    assert report.stats["router_scrape_failures"] >= 1
