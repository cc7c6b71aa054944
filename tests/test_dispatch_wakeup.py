"""Event-driven dispatch (master/wakeup.py): a result that leaves a worker
with nothing queued behind the frame in hand starts the next dispatch pass
at once; the tick is only the timeout.

Every test here patches the tick to a second or more and then holds the
loop to a small fraction of it, so that only the wake-up can explain what
is seen. None sleeps as long as a tick.
"""

import asyncio
import logging
import time

import pytest

from tpu_render_cluster.harness.local import _run, _run_multi_job
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.jobs.tiles import WorkUnit
from tpu_render_cluster.master import strategies
from tpu_render_cluster.master.cluster import ClusterManager
from tpu_render_cluster.master.queue_mirror import FrameOnWorker, WorkerQueueMirror
from tpu_render_cluster.master.state import ClusterManagerState
from tpu_render_cluster.master.wakeup import TRIGGERS, DispatchWakeup
from tpu_render_cluster.master.worker_handle import WorkerHandle
from tpu_render_cluster.obs import MetricsRegistry
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.sched.manager import JobManager, SchedulerConfig
from tpu_render_cluster.sched.models import JOB_FINISHED, JobSpec
from tpu_render_cluster.sched.tickprof import TickProfiler
from tpu_render_cluster.utils.cancellation import CancellationToken
from tpu_render_cluster.utils.logging import WorkerLogger
from tpu_render_cluster.worker.backends.mock import MockBackend

LONG_TICK = 2.0  # what a loop would wait if only its tick woke it
SOON = 0.25  # an eighth of it: generous for a loaded CI host, far from a tick


def make_job(name: str, frames: int, *, start: int = 1, workers: int = 1, strategy=None) -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description="dispatch wake-up test job",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=start,
        frame_range_to=start + frames - 1,
        wait_for_number_of_workers=workers,
        frame_distribution_strategy=strategy or DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )


class LocalHandle(WorkerHandle):
    """A master-side handle with no socket: ``queue_frame`` acknowledges at
    once, and a test plays the worker by handing ``finish`` the events.
    The queue mirror, the frame table and the wake-up rule are the real
    ``WorkerHandle``'s."""

    def __init__(self, worker_id: int, state: ClusterManagerState, wakeup: DispatchWakeup | None) -> None:
        self.worker_id = worker_id
        self.state = state
        self._state_resolver = None
        self._wakeup = wakeup
        self.is_dead = False
        self.drained = False
        self.metrics = None
        self.span_tracer = None
        self.queue = WorkerQueueMirror()
        self._rendering_started_at = {}
        self._completion_observations = []
        self._on_frame_complete = None
        self._on_unit_latency = None
        self.logger = WorkerLogger(logging.getLogger("test"), f"{worker_id:08x}", "test")
        self.queued: list[tuple[int, float, int]] = []  # (frame, when, depth found)

    async def queue_frame(self, job, unit, **_ignored) -> None:
        if isinstance(unit, int):
            unit = WorkUnit(unit)
        await asyncio.sleep(0)  # an RPC is an await point
        self.queued.append((unit.frame_index, time.perf_counter(), len(self.queue)))
        self.queue.add(FrameOnWorker(unit.frame_index, queued_at=time.time(), job_name=job.job_name))
        self.state.mark_frame_as_queued(unit, self.worker_id, time.time())
        if self._wakeup is not None:
            self._wakeup.count_dispatched_frame()

    def mirror(self, job_name: str, frames) -> None:
        for frame in frames:
            self.queue.add(FrameOnWorker(frame, queued_at=time.time(), job_name=job_name))
            self.state.mark_frame_as_queued(WorkUnit(frame), self.worker_id, time.time())

    def finish(self, job_name: str, frame: int) -> float:
        self._apply_finished_event(pm.WorkerFrameQueueItemFinishedEvent.new_ok(job_name, frame))
        return time.perf_counter()


async def until(predicate, limit: float = SOON) -> float:
    """Poll for ``predicate`` at 1 ms; returns how long it took, and fails
    past ``limit``."""
    started = time.perf_counter()
    while not predicate():
        assert time.perf_counter() - started < limit, f"not within {limit} s"
        await asyncio.sleep(0.001)
    return time.perf_counter() - started


# ---------------------------------------------------------------------------
# the wake-up itself


def test_a_burst_of_signals_is_one_early_wake_and_a_lost_one_costs_a_tick():
    async def scenario():
        wakeup = DispatchWakeup()
        assert wakeup.trigger == "tick"
        for _ in range(5):
            wakeup.set()
        started = time.perf_counter()
        assert await wakeup.wait(LONG_TICK) == "event"
        assert time.perf_counter() - started < SOON
        assert not wakeup.is_set()
        # nothing set since: the next wait lasts its whole (short) timeout
        started = time.perf_counter()
        assert await wakeup.wait(0.03) == "tick"
        assert time.perf_counter() - started >= 0.025
        assert wakeup.trigger == "tick"
        # a signal while the loop waits ends the wait
        asyncio.get_running_loop().call_later(0.01, wakeup.set)
        started = time.perf_counter()
        assert await wakeup.wait(LONG_TICK) == "event"
        assert time.perf_counter() - started < SOON

    asyncio.run(scenario())


@pytest.mark.parametrize(
    "left_queued, wakes",
    [(3, False), (2, False), (1, True), (0, True)],
    ids=["leaves-3", "leaves-2", "leaves-1", "leaves-0"],
)
def test_a_finished_event_wakes_the_loop_only_when_at_most_one_frame_is_left(left_queued, wakes):
    job = make_job("depth", 8)
    state = ClusterManagerState(job)
    wakeup = DispatchWakeup()
    handle = LocalHandle(7, state, wakeup)
    handle.mirror("depth", range(1, left_queued + 2))
    handle.finish("depth", 1)
    assert len(handle.queue) == left_queued
    assert wakeup.is_set() is wakes


def test_the_frame_left_counts_whether_or_not_its_rendering_event_has_arrived():
    job = make_job("order", 4)
    state = ClusterManagerState(job)
    wakeup = DispatchWakeup()
    handle = LocalHandle(7, state, wakeup)
    handle.mirror("order", [1, 2])
    # the worker has already taken frame 2 in hand and said so
    handle._apply_rendering_event(pm.WorkerFrameQueueItemRenderingEvent("order", 2))
    handle.finish("order", 1)
    assert wakeup.is_set()


def test_a_bare_handle_without_a_wakeup_applies_events_as_before():
    job = make_job("bare", 2)
    state = ClusterManagerState(job)
    handle = LocalHandle(7, state, None)
    handle.mirror("bare", [1])
    handle.finish("bare", 1)
    assert state.finished_count() == 1


def test_a_ready_event_and_a_new_worker_wake_the_loop():
    """``handle_ready`` and ``_register_new_worker`` set the wake-up: seen
    through a served job on a one-second tick whose first frame goes out
    well inside it (the worker connects after the job was submitted, and
    its ready answer comes after the admission's pass)."""
    spec = JobSpec(job=make_job("ready", 3))
    seen: dict = {}

    async def scenario():
        started = time.perf_counter()
        _traces, job_ids, manager, _workers = await _run_multi_job(
            [spec],
            [MockBackend(render_seconds=0.005)],
            manager_factory=lambda: JobManager(
                "127.0.0.1", 0, metrics=MetricsRegistry(),
                config=SchedulerConfig(tick_seconds=LONG_TICK),
            ),
        )
        seen["elapsed"] = time.perf_counter() - started
        seen["run"] = manager._runs[job_ids[0]]
        seen["metrics"] = manager.metrics.snapshot()

    asyncio.run(asyncio.wait_for(scenario(), 60))
    assert seen["run"].status == JOB_FINISHED
    # submit -> (tick pass admits, maybe one tick late) -> announce -> ready
    # -> dispatch x3 -> finalize: at most one whole tick in all of it, where
    # ticks alone would take four or more.
    assert seen["elapsed"] < LONG_TICK + 4 * SOON
    series = seen["metrics"]["master_dispatch_frames_total"]["series"]
    assert series["trigger=event"] + series["trigger=tick"] == 3
    assert series["trigger=event"] >= 2


# ---------------------------------------------------------------------------
# naive-fine


def run_naive_fine(monkeypatch, job, state, handles, wakeup, play):
    monkeypatch.setattr(strategies, "NAIVE_FINE_TICK", LONG_TICK)
    passes = []

    def workers_fn():
        passes.append(time.perf_counter())
        return handles

    async def scenario():
        strategy = asyncio.create_task(
            strategies.naive_fine_strategy(job, state, workers_fn, CancellationToken(), wakeup)
        )
        await play(passes)
        await asyncio.wait_for(strategy, SOON)

    asyncio.run(asyncio.wait_for(scenario(), 30))


def test_naive_fine_queues_the_next_frame_on_the_finished_event_not_at_its_tick(monkeypatch):
    job = make_job("fine", 4)
    state = ClusterManagerState(job)
    wakeup = DispatchWakeup()
    handle = LocalHandle(1, state, wakeup)
    waits = []

    async def play(_passes):
        for frame in (1, 2, 3):
            await until(lambda: len(handle.queued) == frame)
            await asyncio.sleep(0.01)  # the frame "renders"
            finished_at = handle.finish("fine", frame)
            await until(lambda: len(handle.queued) == frame + 1)
            waits.append(handle.queued[frame][1] - finished_at)
        await asyncio.sleep(0.01)
        handle.finish("fine", 4)  # the last result also ends the strategy at once

    started = time.perf_counter()
    run_naive_fine(monkeypatch, job, state, [handle], wakeup, play)
    assert [frame for frame, _when, _depth in handle.queued] == [1, 2, 3, 4]
    assert max(waits) < SOON, waits  # it reads 1-2 ms; the tick is 2 s
    assert time.perf_counter() - started < LONG_TICK / 2
    assert state.all_frames_finished()


def test_naive_fine_never_queues_onto_a_worker_that_holds_a_frame(monkeypatch):
    job = make_job("guard", 6)
    state = ClusterManagerState(job)
    wakeup = DispatchWakeup()
    fast, slow = LocalHandle(1, state, wakeup), LocalHandle(2, state, wakeup)

    async def play(_passes):
        await until(lambda: len(fast.queued) == 1 and len(slow.queued) == 1)
        held = slow.queued[0][0]
        for _ in range(4):
            # results from the fast worker wake the loop again and again
            # while the slow one still holds its first frame; so does a
            # signal nothing stands behind
            frame = fast.queued[-1][0]
            count = len(fast.queued)
            fast.finish("guard", frame)
            wakeup.set()
            await until(lambda: len(fast.queued) == count + 1)
        assert len(slow.queued) == 1
        fast.finish("guard", fast.queued[-1][0])
        slow.finish("guard", held)

    run_naive_fine(monkeypatch, job, state, [fast, slow], wakeup, play)
    for handle in (fast, slow):
        assert all(depth == 0 for _frame, _when, depth in handle.queued), handle.queued
    assert len(fast.queued) == 5 and len(slow.queued) == 1
    assert state.all_frames_finished()


def test_naive_fine_answers_a_burst_of_results_with_one_early_pass(monkeypatch):
    job = make_job("burst", 6, workers=3)
    state = ClusterManagerState(job)
    wakeup = DispatchWakeup()
    handles = [LocalHandle(i, state, wakeup) for i in (1, 2, 3)]
    counted: dict = {}

    async def play(passes):
        await until(lambda: all(len(h.queued) == 1 for h in handles))
        await asyncio.sleep(0.01)
        counted["before"] = len(passes)
        for handle in handles:  # three results in one turn of the loop
            handle.finish("burst", handle.queued[0][0])
        await until(lambda: all(len(h.queued) == 2 for h in handles))
        await asyncio.sleep(0.02)
        counted["after"] = len(passes)
        for handle in handles:
            handle.finish("burst", handle.queued[1][0])

    run_naive_fine(monkeypatch, job, state, handles, wakeup, play)
    assert counted["after"] - counted["before"] == 1
    assert state.all_frames_finished()


def test_a_signal_that_never_comes_costs_naive_fine_its_tick_and_no_more(monkeypatch):
    """Handles that set nothing (a lost signal): the loop is the old loop,
    a pass a tick."""
    monkeypatch.setattr(strategies, "NAIVE_FINE_TICK", 0.01)
    job = make_job("lost", 2)
    state = ClusterManagerState(job)
    handle = LocalHandle(1, state, None)
    wakeup = DispatchWakeup()

    async def scenario():
        strategy = asyncio.create_task(
            strategies.naive_fine_strategy(job, state, lambda: [handle], CancellationToken(), wakeup)
        )
        for frame in (1, 2):
            await until(lambda: len(handle.queued) == frame)
            handle.finish("lost", frame)
        await asyncio.wait_for(strategy, SOON)

    asyncio.run(asyncio.wait_for(scenario(), 30))
    assert state.all_frames_finished() and wakeup.trigger == "tick"


@pytest.mark.parametrize(
    "strategy, queue_depth",
    [
        (DistributionStrategy.naive_fine(), 1),
        (DistributionStrategy.eager_naive_coarse(4), 4),
    ],
    ids=["naive-fine", "eager-naive-coarse"],
)
def test_a_real_job_counts_every_frame_once_and_only_a_shallow_queue_is_event_driven(monkeypatch, strategy, queue_depth):
    """Through real sockets and a mock worker. naive-fine on a 2 s tick
    ends in a fraction of one tick because results wake it; a queue of 4
    keeps its tick (here 10 ms) and hands out every frame on a tick."""
    monkeypatch.setattr(strategies, "NAIVE_FINE_TICK", LONG_TICK)
    monkeypatch.setattr(strategies, "EAGER_COARSE_TICK", 0.01)
    frames = 8
    job = make_job("real", frames, strategy=strategy)
    seen: dict = {}

    async def scenario():
        started = time.perf_counter()
        _master, _traces, manager, _workers = await _run(job, [MockBackend(render_seconds=0.005)])
        seen["elapsed"] = time.perf_counter() - started
        seen["series"] = manager.metrics.snapshot()["master_dispatch_frames_total"]["series"]
        seen["state"] = manager.state

    asyncio.run(asyncio.wait_for(scenario(), 60))
    assert seen["state"].all_frames_finished()
    series = seen["series"]
    assert series["trigger=event"] + series["trigger=tick"] == frames
    if queue_depth == 1:
        assert series["trigger=event"] >= frames - 1  # the first pass is a tick's
        assert seen["elapsed"] < LONG_TICK
    else:
        # the tail of the job runs the queue down to one frame and sets the
        # signal, but nobody waits on it: every frame is a tick's
        assert series["trigger=event"] == 0


# ---------------------------------------------------------------------------
# the service loop


def serve(specs, backends, config, *, driver=None):
    out: dict = {}

    async def scenario():
        started = time.perf_counter()
        _traces, job_ids, manager, _workers = await _run_multi_job(
            specs,
            backends,
            manager_factory=lambda: JobManager(
                "127.0.0.1", 0, metrics=MetricsRegistry(), config=config
            ),
            driver=driver,
        )
        out.update(
            elapsed=time.perf_counter() - started,
            manager=manager,
            runs=[manager._runs[job_id] for job_id in job_ids],
            metrics=manager.metrics.snapshot(),
        )

    asyncio.run(asyncio.wait_for(scenario(), 120))
    return out


def pass_counts(snapshot: dict) -> dict:
    """Observations of ``sched_tick_seconds`` by phase (0 for a phase that
    never ran: a served job's every pass may be an event's)."""
    series = snapshot["sched_tick_seconds"]["series"]
    return {
        phase: series.get(f"phase={phase}", {"count": 0})["count"]
        for phase in ("total", "event_total", "dispatch")
    }


def test_the_service_refills_a_queue_of_two_and_finalizes_on_the_result_not_at_its_tick():
    frames = 12
    out = serve(
        [JobSpec(job=make_job("refill", frames))],
        [MockBackend(render_seconds=0.01)],
        SchedulerConfig(tick_seconds=LONG_TICK, target_queue_size=2),
    )
    (run,) = out["runs"]
    assert run.status == JOB_FINISHED and run.state.finished_count() == frames
    series = out["metrics"]["master_dispatch_frames_total"]["series"]
    assert series["trigger=event"] + series["trigger=tick"] == frames
    # ticks alone hand out 2 frames a pass: six passes, 10 s and more
    assert series["trigger=event"] >= frames - 2
    assert out["elapsed"] < 2 * LONG_TICK
    # the job is reported finished when its last result is taken
    phases = out["metrics"]["sched_job_phase_seconds"]["series"]
    last = phases["phase=last_result_to_finished"]
    assert last["count"] == 1 and last["sum"] < SOON
    # event passes are profiled apart from the timer's ticks
    passes = pass_counts(out["metrics"])
    assert passes["event_total"] >= frames - 2
    assert passes["total"] <= 3
    assert passes["dispatch"] == passes["event_total"] + passes["total"]


def test_a_deep_service_queue_is_refilled_by_ticks_alone():
    """Under a queue of 4 and frames of six ticks a result leaves 3 frames
    behind: no wake-up while there is anything to hand out. Only the job's
    tail (the pool dry, the queue running down) sets the signal, and those
    passes have no frame to hand out."""
    frames = 10
    out = serve(
        [JobSpec(job=make_job("deep", frames))],
        [MockBackend(render_seconds=0.06)],
        SchedulerConfig(tick_seconds=0.01, target_queue_size=4),
    )
    (run,) = out["runs"]
    assert run.status == JOB_FINISHED
    series = out["metrics"]["master_dispatch_frames_total"]["series"]
    # the first frames go out on the pass that the worker's ready answer
    # woke (an empty queue): 4 at most; every refill after it is a tick's
    assert series["trigger=event"] <= 4
    assert series["trigger=event"] + series["trigger=tick"] == frames
    assert series["trigger=tick"] >= frames - 4


def test_verify_mode_agrees_on_every_pick_of_event_woken_passes():
    """``verify`` raises out of ``serve()`` on any heap/scan divergence."""
    specs = [
        JobSpec(job=make_job("verify-a", 12, workers=2), weight=2.0),
        JobSpec(job=make_job("verify-b", 12, start=101, workers=2), weight=1.0),
        JobSpec(job=make_job("verify-c", 6, start=201, workers=2), priority=1),
    ]
    out = serve(
        specs,
        [MockBackend(render_seconds=0.004) for _ in range(2)],
        SchedulerConfig(tick_seconds=LONG_TICK, tick_mode="verify"),
    )
    assert [run.status for run in out["runs"]] == [JOB_FINISHED] * 3
    series = out["metrics"]["master_dispatch_frames_total"]["series"]
    assert series["trigger=event"] >= 20
    preempted = sum(run.preemptions for run in out["runs"])
    assert series["trigger=event"] + series["trigger=tick"] == 30 + preempted


# ---------------------------------------------------------------------------
# the counter and the profiler


@pytest.mark.parametrize("service", [False, True], ids=["run-job", "serve"])
def test_both_triggers_are_on_the_masters_scrape_at_zero_from_the_start(service):
    registry = MetricsRegistry()
    if service:
        JobManager("127.0.0.1", 0, metrics=registry)
    else:
        ClusterManager("127.0.0.1", 0, make_job("zero", 2), metrics=registry)
    series = registry.snapshot()["master_dispatch_frames_total"]["series"]
    assert series == {f"trigger={trigger}": 0.0 for trigger in TRIGGERS}
    from tpu_render_cluster.obs.prometheus import render_prometheus

    text = render_prometheus(registry.snapshot())
    for trigger in TRIGGERS:
        assert f'master_dispatch_frames_total{{trigger="{trigger}"}} 0' in text


def test_an_event_pass_stays_out_of_the_ticks_total_and_budget():
    registry = MetricsRegistry()
    profiler = TickProfiler(registry, None, tick_budget_seconds=0.05)
    profiler.begin_tick()
    with profiler.phase("dispatch"):
        pass
    profiler.end_tick()
    budget = registry.snapshot()["sched_tick_budget_ratio"]["series"][""]
    for _ in range(3):
        profiler.begin_tick(event_woken=True)
        with profiler.phase("dispatch"):
            time.sleep(0.002)
        profiler.end_tick()
    snapshot = registry.snapshot()
    series = snapshot["sched_tick_seconds"]["series"]
    assert series["phase=total"]["count"] == 1
    assert series["phase=event_total"]["count"] == 3
    assert series["phase=dispatch"]["count"] == 4
    assert profiler.ticks == 1
    assert snapshot["sched_tick_budget_ratio"]["series"][""] == budget
