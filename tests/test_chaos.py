"""Chaos-engine tests: seeded fault plans, injection seams, and the
exactly-once invariants of a faulted cluster.

The fast deterministic subset runs in tier-1 (one full seeded chaos run +
unit tests for the race windows the ISSUE names); the randomized
multi-seed sweep is additionally marked ``slow``.
"""

import asyncio
import json
import time
from types import SimpleNamespace

import pytest

from tpu_render_cluster.chaos import (
    ChaosTimings,
    FaultEvent,
    FaultPlan,
    run_chaos_job,
)
from tpu_render_cluster.chaos.invariants import check_invariants, ledger_stats
from tpu_render_cluster.chaos.plan import (
    KIND_CRASH_AFTER_RESULT,
    KIND_CRASH_BEFORE_RESULT,
    KIND_DUPLICATE_SEND,
    KIND_PARTITION,
    KIND_SLOW_RENDER,
)
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.master.cluster import ClusterManager
from tpu_render_cluster.master.queue_mirror import FrameOnWorker, WorkerQueueMirror
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.master.state import ClusterManagerState, FrameStatus
from tpu_render_cluster.master.strategies import steal_frame
from tpu_render_cluster.master.worker_handle import WorkerHandle
from tpu_render_cluster.obs import MetricsRegistry, validate_trace_file
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.transport.faults import (
    PASS_DECISION,
    SEND_ACTION_DROP,
    SEND_ACTION_DUPLICATE,
    FaultyConnection,
    SendDecision,
)
from tpu_render_cluster.transport.ws import websocket_accept, websocket_connect

pytestmark = pytest.mark.chaos

ACCEPTANCE_SEED = 1234


def make_job(frames: int = 4, workers: int = 1) -> BlenderJob:
    return BlenderJob(
        job_name="chaos-unit",
        job_description="chaos unit test",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=workers,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )


# ---------------------------------------------------------------------------
# FaultPlan: seeded reproducibility + config surfaces


def test_same_seed_reproduces_identical_schedule():
    a = FaultPlan.generate(ACCEPTANCE_SEED, 3)
    b = FaultPlan.generate(ACCEPTANCE_SEED, 3)
    assert a.events == b.events
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != FaultPlan.generate(ACCEPTANCE_SEED + 1, 3).fingerprint()


def test_generated_plan_covers_required_fault_classes():
    plan = FaultPlan.generate(ACCEPTANCE_SEED, 3)
    kinds = plan.kinds()
    assert kinds & {KIND_CRASH_BEFORE_RESULT, KIND_CRASH_AFTER_RESULT}
    assert KIND_PARTITION in kinds
    assert KIND_DUPLICATE_SEND in kinds
    assert KIND_SLOW_RENDER in kinds
    assert plan.expected_evictions() >= 1


def test_plan_refuses_unsurvivable_configs():
    with pytest.raises(ValueError):
        FaultPlan.generate(0, 2, kills=1, wedges=1)  # nobody left alive


def test_unknown_fault_kind_rejected_with_vocabulary(tmp_path):
    """A typo'd fault kind in a TOML plan fails at load time with the
    valid vocabulary in the message — it must never produce a plan whose
    fault silently never fires."""
    plan_path = tmp_path / "typo.toml"
    plan_path.write_text(
        """
seed = 1
workers = 2

[[events]]
kind = "drop_snd"
target = 0
"""
    )
    with pytest.raises(ValueError) as excinfo:
        FaultPlan.from_toml(plan_path)
    message = str(excinfo.value)
    assert "drop_snd" in message
    for kind in ("drop_send", "kill_socket", "slow_render", "drain"):
        assert kind in message
    # Same guard on direct construction.
    with pytest.raises(ValueError, match="Valid kinds"):
        FaultEvent(kind="partitionn", target=0)


def test_plan_toml_roundtrip(tmp_path):
    plan_path = tmp_path / "plan.toml"
    plan_path.write_text(
        """
seed = 9
workers = 2

[[events]]
kind = "partition"
target = 1
at_seconds = 0.5
duration_seconds = 0.25

[timings]
heartbeat_interval = 0.2
"""
    )
    plan = FaultPlan.from_toml(plan_path)
    assert plan.seed == 9
    assert plan.events == (
        FaultEvent(
            kind="partition", target=1, at_seconds=0.5, duration_seconds=0.25
        ),
    )
    assert plan.timings.heartbeat_interval == 0.2
    # Explicit dict round-trip preserves the fingerprint.
    assert FaultPlan.from_dict(plan.to_dict()).fingerprint() == plan.fingerprint()


def test_plan_toml_generate_table(tmp_path):
    plan_path = tmp_path / "plan.toml"
    plan_path.write_text(
        """
seed = 4
workers = 3

[generate]
kills = 1
partitions = 0
duplicate_sends = 0
stragglers = 0
wedges = 0
drops = 0
dispatch_delays = 0
"""
    )
    plan = FaultPlan.from_toml(plan_path)
    assert len(plan.events) == 1
    assert plan.events[0].kind in (
        KIND_CRASH_BEFORE_RESULT,
        KIND_CRASH_AFTER_RESULT,
    )
    # The generate table is seeded too.
    assert plan.events == FaultPlan.from_toml(plan_path).events


def test_plan_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv("TRC_CHAOS_PLAN", raising=False)
    monkeypatch.setenv("TRC_CHAOS_SEED", "42")
    monkeypatch.setenv("TRC_CHAOS_WORKERS", "4")
    plan = FaultPlan.from_env()
    assert plan.seed == 42 and plan.workers == 4
    plan_path = tmp_path / "env-plan.toml"
    plan_path.write_text("seed = 5\nworkers = 2\n\n[generate]\nkills = 0\npartitions = 1\nduplicate_sends = 0\nstragglers = 0\nwedges = 0\ndrops = 0\ndispatch_delays = 0\n")
    monkeypatch.setenv("TRC_CHAOS_PLAN", str(plan_path))
    assert FaultPlan.from_env().seed == 5


# ---------------------------------------------------------------------------
# FaultyConnection: transport-seam unit tests


class _ScriptedController:
    """FaultController that replays a fixed decision list."""

    def __init__(self, decisions):
        self.decisions = list(decisions)
        self.after_sends = []

    def check_gate(self):
        pass

    def on_send(self, text):
        return self.decisions.pop(0) if self.decisions else PASS_DECISION

    def after_send(self, text):
        self.after_sends.append(text)


def test_faulty_connection_drop_duplicate_passthrough():
    async def scenario():
        received = []
        done = asyncio.Event()

        async def server(reader, writer):
            ws = await websocket_accept(reader, writer)
            while len(received) < 3:
                received.append(await ws.receive_text())
            done.set()

        server_obj = await asyncio.start_server(server, "127.0.0.1", 0)
        port = server_obj.sockets[0].getsockname()[1]
        controller = _ScriptedController(
            [
                SendDecision(SEND_ACTION_DUPLICATE),
                SendDecision(SEND_ACTION_DROP),
                PASS_DECISION,
            ]
        )
        ws = FaultyConnection(
            await websocket_connect("127.0.0.1", port), controller
        )
        await ws.send_text("one")  # duplicated
        await ws.send_text("two")  # dropped in flight
        await ws.send_text("three")  # passes
        await asyncio.wait_for(done.wait(), 5)
        await ws.close()
        server_obj.close()
        # The dropped send never ran after_send; the others did.
        assert received == ["one", "one", "three"]
        assert controller.after_sends == ["one", "three"]

    asyncio.run(asyncio.wait_for(scenario(), 30))


def test_on_send_counts_every_matching_fault():
    # Two send faults matching the same message type on one slot: the one
    # that doesn't fire first must still advance its ordinal counter, so
    # its own nth trigger lands where the plan's schedule declares.
    from tpu_render_cluster.chaos.inject import WorkerChaosController
    from tpu_render_cluster.chaos.plan import FINISHED_EVENT_TYPE, KIND_DROP_SEND

    async def scenario():
        controller = WorkerChaosController(
            0,
            (
                FaultEvent(
                    kind=KIND_DROP_SEND,
                    target=0,
                    nth=1,
                    match_message_type=FINISHED_EVENT_TYPE,
                ),
                FaultEvent(
                    kind=KIND_DUPLICATE_SEND,
                    target=0,
                    nth=2,
                    match_message_type=FINISHED_EVENT_TYPE,
                ),
            ),
        )
        finished = pm.encode_message(
            pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 1)
        )
        assert controller.on_send(finished).action == SEND_ACTION_DROP
        # Message 2 is the duplicate's nth=2 even though message 1 was
        # consumed by the drop.
        assert controller.on_send(finished).action == SEND_ACTION_DUPLICATE
        assert controller.on_send(finished) is PASS_DECISION

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Satellite: the duplicate-result race (master/state.py:118-136)


def _make_handle(state, worker_id):
    connection = SimpleNamespace(last_known_address="127.0.0.1:0")
    return WorkerHandle(
        worker_id, connection, state, metrics=state_metrics_registry(state)
    )


_REGISTRIES = {}


def state_metrics_registry(state):
    return _REGISTRIES.setdefault(id(state), MetricsRegistry())


def test_duplicate_and_late_results_keep_ledger_exact():
    # The evicted worker's job-finished/frame-result arrives AFTER the
    # frame was requeued and finished elsewhere: per-frame status and
    # _finished_count must stay correct, with the collision accounted.
    state = ClusterManagerState(make_job(frames=3))
    a = _make_handle(state, 0xAAAA0001)
    b = _make_handle(state, 0xBBBB0002)
    now = time.time()

    # Frame 1: normal path on A, then a duplicated delivery of the ok.
    state.mark_frame_as_queued(1, a.worker_id, now)
    a.queue.add(FrameOnWorker(1, queued_at=now))
    a._apply_rendering_event(pm.WorkerFrameQueueItemRenderingEvent("j", 1))
    ok_1 = pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 1)
    a._apply_finished_event(ok_1)
    assert state.frames[WorkUnit(1)].status is FrameStatus.FINISHED
    assert state.finished_count() == 1
    a._apply_finished_event(ok_1)  # duplicated send
    assert state.finished_count() == 1  # no double-count

    # Frame 2: queued on A, A evicted (frame requeued), re-queued and
    # finished on B — then A's late ok arrives.
    state.mark_frame_as_queued(2, a.worker_id, now)
    a.queue.add(FrameOnWorker(2, queued_at=now))
    a.is_dead = True
    state.return_frame_to_pending(2, "eviction")
    a.queue.clear()
    state.mark_frame_as_queued(2, b.worker_id, now)
    b.queue.add(FrameOnWorker(2, queued_at=now))
    a._apply_finished_event(pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 2))
    assert state.frames[WorkUnit(2)].status is FrameStatus.FINISHED  # late ok accepted
    assert state.finished_count() == 2
    b._apply_finished_event(pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 2))
    assert state.finished_count() == 2  # B's copy absorbed as duplicate

    # Frame 3: queued on B; evicted A's late ERRORED result must not
    # requeue a frame it no longer owns.
    state.mark_frame_as_queued(3, b.worker_id, now)
    b.queue.add(FrameOnWorker(3, queued_at=now))
    a._apply_finished_event(
        pm.WorkerFrameQueueItemFinishedEvent.new_errored("j", 3, "boom")
    )
    assert state.frames[WorkUnit(3)].status is FrameStatus.QUEUED_ON_WORKER
    assert state.frames[WorkUnit(3)].worker_id == b.worker_id
    assert state.pending_count() == 0

    # The exactly-once ledger: ok_results - duplicates == frames finished.
    snapshot = state_metrics_registry(state).snapshot()
    ledger = ledger_stats(snapshot)
    assert ledger["ok_results"] - ledger["duplicate_results"] == 2
    assert ledger["duplicate_results"] == 2  # frame 1 dup + frame 2's B copy
    assert ledger["late_results"] == 1
    assert ledger["stale_results"] == 1


# ---------------------------------------------------------------------------
# Satellite: steal-during-eviction (master/strategies.py:209-232)


class _FakeWorker:
    def __init__(self, worker_id, state, *, unqueue_hook=None):
        self.worker_id = worker_id
        self.state = state
        self.is_dead = False
        self.frames_stolen_count = 0
        self.queue = WorkerQueueMirror()
        self.queued_calls = []
        self._unqueue_hook = unqueue_hook

    async def unqueue_frame(self, job_name, unit):
        if self._unqueue_hook is not None:
            await self._unqueue_hook(self, unit.frame_index)
        self.queue.remove(unit.frame_index, tile=unit.tile)
        return pm.FRAME_QUEUE_REMOVE_RESULT_REMOVED

    async def queue_frame(self, job, unit, *, stolen_from=None):
        self.queued_calls.append(unit.frame_index)
        now = time.time()
        self.queue.add(FrameOnWorker(unit.frame_index, queued_at=now, tile=unit.tile))
        self.state.mark_frame_as_queued(
            unit, self.worker_id, now, stolen_from=stolen_from
        )


def _steal_setup():
    job = make_job(frames=6)
    state = ClusterManagerState(job)
    thief = _FakeWorker(0x7001, state)
    victim = _FakeWorker(0x7002, state)
    now = time.time()
    # Assign in deque order like the strategy loop does (each assignment
    # pops its pending entry): 1-4 to the thief, 5 to the victim.
    for index in (1, 2, 3, 4):
        assert state.next_pending_unit() == WorkUnit(index)
        state.mark_frame_as_queued(index, thief.worker_id, now)
    assert state.next_pending_unit() == WorkUnit(5)
    state.mark_frame_as_queued(5, victim.worker_id, now)
    victim.queue.add(FrameOnWorker(5, queued_at=now))
    return job, state, thief, victim


def test_steal_aborts_when_eviction_already_requeued():
    # Victim dies between steal selection and the requeue; the eviction
    # sweep already returned the frame. It must be pending EXACTLY once
    # and must not land on the thief as well.
    async def scenario():
        async def evict_during_rpc(victim, frame_index):
            victim.is_dead = True
            victim.state.return_frame_to_pending(frame_index, "eviction")
            victim.queue.clear()

        job, state, thief, victim = _steal_setup()
        victim._unqueue_hook = evict_during_rpc
        assert await steal_frame(job, state, thief, victim, 5) is False
        assert thief.queued_calls == []
        assert state.frames[WorkUnit(5)].status is FrameStatus.PENDING
        assert list(state._pending).count(WorkUnit(5)) == 1

    asyncio.run(scenario())


def test_steal_requeues_when_eviction_cannot_see_the_frame():
    # The unqueue RPC removed the frame from the victim's mirror before
    # the eviction sweep ran: the sweep can no longer see it, so the
    # aborted steal itself must return it to pending (or it is lost).
    async def scenario():
        async def die_without_evicting(victim, frame_index):
            victim.is_dead = True  # mirror sweep happens later, finds nothing

        job, state, thief, victim = _steal_setup()
        victim._unqueue_hook = die_without_evicting
        assert await steal_frame(job, state, thief, victim, 5) is False
        assert thief.queued_calls == []
        assert state.frames[WorkUnit(5)].status is FrameStatus.PENDING
        assert list(state._pending).count(WorkUnit(5)) == 1

    asyncio.run(scenario())


def test_steal_proceeds_when_victim_alive():
    async def scenario():
        job, state, thief, victim = _steal_setup()
        assert await steal_frame(job, state, thief, victim, 5) is True
        assert thief.queued_calls == [5]
        assert state.frames[WorkUnit(5)].status is FrameStatus.QUEUED_ON_WORKER
        assert state.frames[WorkUnit(5)].worker_id == thief.worker_id

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Invariant checker


def test_invariant_checker_flags_violations():
    job = make_job(frames=2)
    state = ClusterManagerState(job)
    manager = SimpleNamespace(state=state, metrics=MetricsRegistry(), workers={})
    plan = FaultPlan(seed=0, workers=1, events=())
    violations = check_invariants(manager, plan)
    assert any("completion" in v for v in violations)
    # Finish both frames and balance the ledger -> clean.
    for index in (1, 2):
        state.mark_frame_as_finished(index)
    manager.metrics.counter(
        "master_frame_results_total", "x", labels=("result",)
    ).inc(2, result="ok")
    assert check_invariants(manager, plan) == []
    # An unbalanced ledger (a double-counted result) is flagged.
    manager.metrics.counter(
        "master_frame_results_total", "x", labels=("result",)
    ).inc(result="ok")
    assert any("exactly-once" in v for v in check_invariants(manager, plan))


# ---------------------------------------------------------------------------
# The acceptance run: a full seeded chaos job on a 3-worker cluster


@pytest.fixture(scope="module")
def acceptance_run(tmp_path_factory):
    results = tmp_path_factory.mktemp("chaos-results")
    plan = FaultPlan.generate(ACCEPTANCE_SEED, 3)
    report = run_chaos_job(plan, frames=24, results_directory=results)
    return plan, report, results


def test_chaos_acceptance_invariants(acceptance_run):
    plan, report, _results = acceptance_run
    assert report.violations == []
    stats = report.stats
    # The plan's required fault classes actually fired.
    fired = stats["faults_injected"]
    assert any(
        kind in fired for kind in (KIND_CRASH_BEFORE_RESULT, KIND_CRASH_AFTER_RESULT)
    )
    assert fired.get(KIND_PARTITION, 0) >= 1
    assert fired.get(KIND_DUPLICATE_SEND, 0) >= 1
    assert fired.get(KIND_SLOW_RENDER, 0) >= 1
    # The cluster delivered every frame exactly once despite them.
    ledger = stats["ledger"]
    assert ledger["ok_results"] - ledger["duplicate_results"] == stats["frames_total"]
    assert ledger["duplicate_results"] >= 1  # the duplicated send was absorbed
    assert ledger["evictions"] == plan.expected_evictions()
    # Re-generating the plan from the same seed reproduces the schedule.
    assert FaultPlan.generate(ACCEPTANCE_SEED, 3).fingerprint() == plan.fingerprint()


def test_chaos_acceptance_artifacts_valid(acceptance_run):
    _plan, report, _results = acceptance_run
    from pathlib import Path

    # Every exported timeline (per-process and merged cluster) holds the
    # trace invariants even though workers died mid-run.
    for key in ("trace_events", "cluster_trace"):
        assert validate_trace_file(report.artifacts[key]) == []
    metrics = json.loads(Path(report.artifacts["metrics"]).read_text())
    assert "metrics" in metrics


def test_chaos_section_in_statistics(acceptance_run):
    _plan, _report, results = acceptance_run
    from tpu_render_cluster.analysis.obs_events import (
        load_obs_artifacts,
        summarize_obs,
    )

    traces, metrics = load_obs_artifacts(results)
    summary = summarize_obs(traces, metrics)
    assert "chaos" in summary
    chaos = summary["chaos"]
    assert chaos["faults_injected"]  # what was done...
    assert "master_worker_evictions_total" in chaos["ledger"]  # ...and survived


# ---------------------------------------------------------------------------
# Graceful drain (SIGTERM path, driven in-process)


def test_graceful_drain_requeues_and_counts_no_eviction(tmp_path):
    plan = FaultPlan.generate(
        21,
        2,
        kills=0,
        partitions=0,
        duplicate_sends=0,
        stragglers=0,
        wedges=0,
        drops=0,
        dispatch_delays=0,
        drains=1,
    )
    assert plan.expected_drains() == 1 and plan.expected_evictions() == 0
    report = run_chaos_job(
        plan, frames=16, render_seconds=0.25, results_directory=tmp_path
    )
    assert report.violations == []
    ledger = report.stats["ledger"]
    assert ledger["drains"] == 1
    assert ledger["evictions"] == 0
    assert ledger["ok_results"] - ledger["duplicate_results"] == 16


# ---------------------------------------------------------------------------
# Randomized sweep (slow tier)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_chaos_randomized_sweep(seed, tmp_path):
    plan = FaultPlan.generate(seed, 3)
    report = run_chaos_job(plan, frames=24, results_directory=tmp_path)
    assert report.violations == []
