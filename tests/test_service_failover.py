"""The pool loses a worker under load, through ``master serve`` on mock workers.

The reconnect window (30 s in a deployment) is patched short, as
``chaos/runner.py`` patches it; no assertion on wall time is tighter than
ten times what it bounds, and every test has a time limit.

- a worker whose socket is aborted without a goodbye: the survivors go on
  rendering and jobs go on being admitted while it is silent, its units come
  back at the window's end with their cause, every job ends with exactly its
  files (``benchmark/reference/plain_service.py``), a write its death cut
  leaves no temporary file behind, and ``status``, the counters and the
  master's timeline say what happened;
- a worker that reconnects inside the window keeps its id and its queue:
  nothing is handed back, nothing rendered twice, and it is told of the jobs
  admitted meanwhile;
- a result that arrives from a worker after its eviction is counted once;
- a job whose barrier the pool cannot meet is reported by ``status``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from collections import Counter

import pytest
from PIL import Image

from benchmark.lib import scrape
from benchmark.reference import plain_service
from tests.test_service import make_job
from tpu_render_cluster.harness.local import _run_multi_job
from tpu_render_cluster.master.state import HANDBACK_CAUSES, FrameStatus
from tpu_render_cluster.obs import MetricsRegistry
from tpu_render_cluster.obs.prometheus import render_prometheus
from tpu_render_cluster.render.image_io import output_path_for_frame, write_image
from tpu_render_cluster.sched.control import handle_request
from tpu_render_cluster.sched.manager import JobManager
from tpu_render_cluster.sched.models import JobSpec
from tpu_render_cluster.transport.reconnect import ReconnectableServerConnection
from tpu_render_cluster.transport.ws import WebSocketClosed
from tpu_render_cluster.utils.paths import parse_with_base_directory_prefix
from tpu_render_cluster.worker.backends.mock import MockBackend

POOL = 4
SIDE = 8  # the mock's frames are real 8x8 JPEGs, so the tree is held to plain_service


class WritingMock(MockBackend):
    """A mock that writes its frames as the real backends do
    (``write_image``: a temporary file renamed into place) and notes when
    it began each."""

    def __init__(self, base, render_seconds: float = 0.02) -> None:
        super().__init__(render_seconds=render_seconds, load_seconds=0.0, save_seconds=0.0)
        self.base = base
        self.began: list[tuple[str, int, float]] = []

    async def render_frame(self, job, frame_index, tile=None):
        self.began.append((job.job_name, frame_index, time.time()))
        times = await super().render_frame(job, frame_index, tile)
        directory = parse_with_base_directory_prefix(job.output_directory_path, self.base)
        path = output_path_for_frame(directory, job.output_file_name_format, job.output_file_format, frame_index)
        await asyncio.to_thread(write_image, path, Image.new("RGB", (SIDE, SIDE), (frame_index % 256, 0, 0)), "JPEG")
        return times


@pytest.fixture
def short_window(monkeypatch):
    def patch(seconds: float) -> float:
        monkeypatch.setattr(ReconnectableServerConnection, "MAX_WAIT_FOR_RECONNECT", seconds)
        return seconds
    return patch


async def status_now(manager) -> dict:
    return (await handle_request(manager, {"op": "status"}))["sched"]


def kill(worker, task) -> None:
    """SIGKILL as a process sees it: the socket closes without a goodbye and
    nothing of the worker runs again."""
    worker._client.close()
    task.cancel()


# -- a worker lost without a goodbye -----------------------------------------------------

WINDOW = 4.0
JOBS, FRAMES = 24, 24


@pytest.fixture(scope="module")
def lost(tmp_path_factory):
    base = tmp_path_factory.mktemp("lost")
    backends = [WritingMock(base) for _ in range(POOL)]
    seen: dict = {"views": [], "tasks": None}

    async def keep_tasks(_manager, _workers, tasks):
        seen["tasks"] = tasks

    async def lose_one(manager, workers):
        while sum(len(b.rendered_frames) for b in backends) < 40:
            await asyncio.sleep(0.005)
        victim = workers[0]
        handle = manager.workers[victim.worker_id]
        while len(handle.queue) < manager.config.target_queue_size:
            await asyncio.sleep(0.001)
        seen.update(victim=victim.worker_id, killed_at=time.time())
        kill(victim, seen["tasks"][0])
        while not handle.is_silent:  # the master reads the closed socket on its next turn
            await asyncio.sleep(0.001)
        # Everything the worker ever sent has been taken by now, so what its
        # mirror holds is what it died with. A write that the death cut: the
        # temporary file of one of those units.
        held = handle.queue.all_frames()[-1]
        run = manager._active_by_name[held.job_name]
        directory = parse_with_base_directory_prefix(run.spec.job.output_directory_path, base)
        directory.mkdir(parents=True, exist_ok=True)
        cut = directory / f".rendered-{held.frame_index:06d}.jpg.k1ll3d.tmp"
        cut.write_bytes(b"\xff\xd8 half a frame")
        seen.update(held=[(f.job_name, f.frame_index) for f in handle.queue.all_frames()], cut=cut)
        while not handle.is_dead:
            seen["views"].append(await status_now(manager))
            await asyncio.sleep(0.05)
        seen["evicted_at"] = time.time()
        seen["views"].append(await status_now(manager))

    saved = ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT
    ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT = WINDOW
    try:
        specs = [JobSpec(job=make_job(f"shot-{i:02d}", 1, FRAMES, None)) for i in range(JOBS)]
        _traces, job_ids, manager, workers = asyncio.run(asyncio.wait_for(_run_multi_job(
            specs, backends,
            manager_factory=lambda: JobManager("127.0.0.1", 0, metrics=MetricsRegistry(), output_base_directory=base),
            on_cluster_started=keep_tasks, driver=lose_one, allow_worker_failures=True, worker_grace=5.0,
        ), 120.0))
    finally:
        ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT = saved
    return {"base": base, "backends": backends, "manager": manager, "job_ids": job_ids, "workers": workers, **seen}


def survivors_went_on(run):
    """No survivor waited for the silent one: each began a frame at least
    every half window while the fourth was silent (a pass that awaited the
    dead worker's socket stops all three for the whole window)."""
    for backend in run["backends"][1:]:
        began = [at for _job, _frame, at in backend.began if run["killed_at"] <= at <= run["evicted_at"]]
        edges = [run["killed_at"], *began, run["evicted_at"]]
        assert max(b - a for a, b in zip(edges, edges[1:])) < WINDOW / 2, edges


def jobs_were_admitted_under_the_silence(run):
    manager = run["manager"]
    admitted = sorted(
        manager._runs[job_id].admitted_at - run["killed_at"] for job_id in run["job_ids"]
        if run["killed_at"] < manager._runs[job_id].admitted_at < run["evicted_at"]
    )
    assert admitted and admitted[0] < WINDOW / 2, admitted
    passes = manager.metrics.histogram("sched_tick_seconds", "", labels=("phase",)).series(phase="pass")
    assert passes.count > 0 and passes.max < WINDOW / 2  # no pass of the loop waited out the window


def its_units_came_back_with_their_cause(run):
    manager = run["manager"]
    reports = asyncio.run(handle_request(manager, {"op": "handbacks"}))["handbacks"]
    evicted = [r for r in reports if r["cause"] == "eviction"]
    assert evicted and {r["worker"] for r in evicted} == {f"{run['victim']:08x}"}
    # what its queue held at the kill is among them (a claim on its way adds to them),
    # but for a unit whose result was already on its way when the socket went
    delivered = {
        (manager._runs[job_id].job_name, entry["frame"])
        for job_id in run["job_ids"] for entry in manager.results_view(job_id)["results"]
        if entry["worker"] == f"{run['victim']:08x}"
    }
    assert set(run["held"]) - delivered <= {(r["job_name"], r["frame"]) for r in evicted}
    assert all(run["evicted_at"] - 1.0 <= r["at"] <= run["evicted_at"] + 1.0 for r in evicted)
    handed_back = manager.metrics.counter("sched_units_handed_back_total", "", labels=("cause",))
    assert handed_back.value(cause="eviction") == len(evicted)
    # the window was kept: not shorter, and not longer by more than a pass or ten
    assert WINDOW - 0.1 <= run["evicted_at"] - run["killed_at"] < WINDOW + 1.0


def every_job_finished_with_exactly_its_files(run):
    manager = run["manager"]
    assert all(manager.job_status(job_id)["status"] == "finished" for job_id in run["job_ids"])
    described = [
        {"name": f"shot-{i:02d}", "directory": f"shot-{i:02d}", "first": 1, "last": FRAMES,
         "name_format": "rendered-######", "file_format": "JPEG", "width": SIDE, "height": SIDE}
        for i in range(JOBS)
    ]
    must, may = plain_service.expected(described, {job["name"] for job in described})
    assert plain_service.compare(run["base"] / "frames", must, may) == []
    # each unit the survivors rendered, they rendered once; what the dead one held, somebody did again
    renders = Counter((job, frame) for backend in run["backends"][1:] for job, frame, _ in backend.began)
    assert set(renders.values()) == {1}
    reports = asyncio.run(handle_request(manager, {"op": "handbacks"}))["handbacks"]
    assert {(r["job_name"], r["frame"]) for r in reports if r["cause"] == "eviction"} <= set(renders)


def a_cut_write_left_nothing_behind(run):
    assert not run["cut"].exists()
    assert [p.name for p in (run["base"] / "frames").rglob(".*")] == []
    assert run["manager"].metrics.counter("master_cut_writes_removed_total", "").value() == 1


def status_said_silent_then_dead(run):
    label = f"{run['victim']:08x}"
    states = [view["workers"][label]["state"] for view in run["views"]]
    assert states[0] == "silent" and states[-1] == "dead" and set(states) == {"silent", "dead"}
    first = run["views"][0]["workers"][label]
    assert 0 < first["units"] <= len(run["held"]) and 0.0 < first["window_left_s"] <= WINDOW
    others = {state["state"] for view in run["views"] for name, state in view["workers"].items() if name != label}
    assert others == {"live"}
    # a job with nothing left but what the silent worker holds says so, and holds no active slot
    waiting = {job["waiting_on"] for view in run["views"] for job in view["jobs"].values() if job["status"] == "running"}
    assert "silent_worker" in waiting
    assert max(len(view["running"]) for view in run["views"]) > run["manager"].config.max_active_jobs
    assert run["manager"].metrics.counter("sched_job_blocked_on_silent_worker_seconds_total", "").value() > 0.0


def the_counters_and_the_timeline_say_it(run):
    manager = run["manager"]
    assert manager.metrics.counter("master_worker_evictions_total", "").value() == 1
    silent_s = manager.metrics.counter("master_worker_silent_seconds_total", "").value()
    assert WINDOW - 0.1 <= silent_s < WINDOW + 1.0
    reconnects = manager.metrics.counter("master_worker_reconnects_total", "", labels=("worker",))
    assert all(reconnects.value(worker=f"{w.worker_id:08x}") == 0 for w in run["workers"])
    spans = [e for e in manager.span_tracer.events() if e.get("name") == "worker silent"]
    assert len(spans) == 1
    assert spans[0]["args"]["worker"] == f"{run['victim']:08x}" and spans[0]["args"]["ended"] == "evicted"
    assert 0 < spans[0]["args"]["units_held"] <= len(run["held"])
    assert WINDOW - 0.1 <= spans[0]["dur"] / 1e6 < WINDOW + 1.0


@pytest.mark.time_limit(180)
@pytest.mark.parametrize("holds", [
    survivors_went_on, jobs_were_admitted_under_the_silence, its_units_came_back_with_their_cause,
    every_job_finished_with_exactly_its_files, a_cut_write_left_nothing_behind, status_said_silent_then_dead,
    the_counters_and_the_timeline_say_it,
], ids=lambda check: check.__name__)
def test_a_worker_lost_without_a_goodbye(lost, holds):
    holds(lost)


def test_the_new_series_read_zero_from_the_services_start():
    manager = JobManager("127.0.0.1", 0, metrics=MetricsRegistry())
    samples = scrape.parse(render_prometheus(manager.metrics.snapshot()))
    for series in (
        "master_worker_evictions_total", "master_worker_silent_seconds_total", "master_cut_writes_removed_total",
        "sched_job_blocked_on_silent_worker_seconds_total", "sched_admission_barrier_unmet_job_units",
    ):
        assert scrape.total(samples, series) == 0.0, series
    for cause in HANDBACK_CAUSES:
        assert scrape.total(samples, "sched_units_handed_back_total", {"cause": cause}) == 0.0, cause


# -- a reconnect inside the window ---------------------------------------------------------


@pytest.mark.time_limit(120)
def test_a_worker_that_reconnects_inside_the_window_keeps_its_id_and_queue(tmp_path, short_window, monkeypatch):
    short_window(20.0)
    monkeypatch.setenv("TRC_BACKOFF_CAP_SECONDS", "0.05")
    monkeypatch.setenv("TRC_MAX_CONNECT_RETRIES", "400")
    backends = [WritingMock(tmp_path, render_seconds=0.03) for _ in range(POOL)]
    cut_off = {"on": False}
    seen: dict = {}

    def partitioned(connection):
        if cut_off["on"]:
            connection.abort()
            raise WebSocketClosed("partitioned")
        return connection

    async def cut_one_off(manager, workers):
        while sum(len(b.rendered_frames) for b in backends) < 30:
            await asyncio.sleep(0.005)
        victim = workers[0]
        handle = manager.workers[victim.worker_id]
        # With its queue full and no queue-add on its way: a request written
        # into a socket that dies under it is lost for good, and its unit
        # comes back as `dispatch_failed` when the RPC's 60 s are over.
        while manager._unacked.get(victim.worker_id) or len(handle.queue) < manager.config.target_queue_size:
            await asyncio.sleep(0.001)
        seen.update(victim=victim.worker_id, held=len(handle.queue))
        victim._connection_wrapper = partitioned
        cut_off["on"] = True
        victim._client.connection.abort()
        while not handle.is_silent:
            await asyncio.sleep(0.002)
        seen["silent_from"] = time.time()
        seen["view"] = await status_now(manager)
        # cut off until a job has been admitted without it (and half a second at least)
        while time.time() - seen["silent_from"] < 0.5 or not any(
            run.admitted_at is not None and run.admitted_at > seen["silent_from"] for run in manager._runs.values()
        ):
            await asyncio.sleep(0.01)
        seen["cut_until"] = time.time()
        cut_off["on"] = False
        while handle.is_silent:
            await asyncio.sleep(0.005)
        seen["back_at"] = time.time()

    specs = [JobSpec(job=make_job(f"shot-{i:02d}", 1, 20, None)) for i in range(16)]
    _traces, job_ids, manager, workers = asyncio.run(asyncio.wait_for(_run_multi_job(
        specs, backends,
        manager_factory=lambda: JobManager("127.0.0.1", 0, metrics=MetricsRegistry(), output_base_directory=tmp_path),
        driver=cut_one_off,
    ), 100.0))
    label = f"{seen['victim']:08x}"
    assert all(manager.job_status(job_id)["status"] == "finished" for job_id in job_ids)
    assert seen["view"]["workers"][label]["state"] == "silent"
    assert 0.5 <= seen["cut_until"] - seen["silent_from"] <= seen["back_at"] - seen["silent_from"] < 15.0
    # the same worker, by its id: none was added, none evicted, one reconnect
    assert len(manager.workers) == POOL and not manager.workers[seen["victim"]].is_dead
    assert manager.metrics.counter("master_worker_evictions_total", "").value() == 0
    assert manager.metrics.counter("master_worker_reconnects_total", "", labels=("worker",)).value(worker=label) == 1
    # nothing was handed back and nothing rendered twice: its queue stayed its own
    reports = asyncio.run(handle_request(manager, {"op": "handbacks"}))["handbacks"]
    assert {r["cause"] for r in reports} <= {"preemption"}  # fair share's own, as in any run of many jobs
    assert manager.metrics.counter("sched_units_handed_back_total", "", labels=("cause",)).value(cause="eviction") == 0
    renders = Counter((job, frame) for backend in backends for job, frame, _ in backend.began)
    assert len(renders) == 16 * 20 and set(renders.values()) == {1}
    assert all(manager.job_status(job_id)["ledger"]["duplicate_results"] == 0 for job_id in job_ids)
    # jobs went on being admitted while it was silent, and it was told of each when it was back
    meanwhile = [
        run for run in manager._runs.values()
        if seen["silent_from"] < run.admitted_at < seen["cut_until"]
    ]
    assert meanwhile and all(seen["victim"] in run.announce_sent for run in meanwhile)
    (span,) = [e for e in manager.span_tracer.events() if e.get("name") == "worker silent"]
    # (a result already on its way when the socket went may have left its queue one shorter)
    assert span["args"]["ended"] == "reconnected" and span["args"]["units_held"] <= seen["held"]


# -- a result after the eviction -------------------------------------------------------------


@pytest.mark.time_limit(120)
def test_a_result_that_arrives_after_its_workers_eviction_is_counted_once(tmp_path):
    backends = [WritingMock(tmp_path, render_seconds=0.3) for _ in range(2)]
    seen: dict = {}

    async def declare_dead_what_still_renders(manager, _workers):
        while not seen:
            await asyncio.sleep(0.005)
            for run in manager._runs.values():
                for unit, record in (run.state.frames.items() if run.state is not None else ()):
                    if record.status is FrameStatus.RENDERING_ON_WORKER:
                        seen.update(unit=(run.job_name, unit.frame_index), worker=record.worker_id)
                        # declared dead with its socket up: its result still arrives
                        await manager.workers[record.worker_id]._mark_dead("declared dead by the test")
                        return

    _traces, job_ids, manager, _workers = asyncio.run(asyncio.wait_for(_run_multi_job(
        [JobSpec(job=make_job("shot", 1, 10, None))], backends,
        manager_factory=lambda: JobManager("127.0.0.1", 0, metrics=MetricsRegistry(), output_base_directory=tmp_path),
        driver=declare_dead_what_still_renders, allow_worker_failures=True, worker_grace=5.0,
    ), 100.0))
    view = manager.job_status(job_ids[0])
    assert view["status"] == "finished" and view["frames_finished"] == 10
    ledger = view["ledger"]
    # its result came after the eviction and was taken (late), or came second (a duplicate):
    # either way every unit counts once, and every render beyond the ten is a counted duplicate
    assert ledger["ok_results"] - ledger["duplicate_results"] == 10
    assert ledger["duplicate_results"] + ledger["late_results"] >= 1
    renders = Counter((job, frame) for backend in backends for job, frame, _ in backend.began)
    assert len(renders) == 10 and sum(renders.values()) - 10 == ledger["duplicate_results"]
    reports = asyncio.run(handle_request(manager, {"op": "handbacks"}))["handbacks"]
    assert (seen["unit"][0], seen["unit"][1], "eviction") in {(r["job_name"], r["frame"], r["cause"]) for r in reports}


# -- a barrier the pool cannot meet ------------------------------------------------------------


@pytest.mark.time_limit(120)
def test_a_job_whose_barrier_the_pool_cannot_meet_is_reported_not_parked(tmp_path):
    backends = [WritingMock(tmp_path) for _ in range(2)]
    seen: dict = {}
    wants_three = dataclasses.replace(make_job("wants-three", 1, 4, None), wait_for_number_of_workers=3)

    async def ask(manager, _workers):
        late = manager.submit(JobSpec(job=wants_three))
        for _ in range(40):
            await asyncio.sleep(0.05)
            seen["view"] = await status_now(manager)
            if seen["view"]["jobs"][late]["waiting_on"]:
                break
        seen["gauge"] = manager.metrics.gauge("sched_admission_barrier_unmet_job_units", "").value()
        assert await manager.cancel_job(late)

    _traces, job_ids, manager, _workers = asyncio.run(asyncio.wait_for(_run_multi_job(
        [JobSpec(job=make_job("shot", 1, 30, None))], backends,
        manager_factory=lambda: JobManager("127.0.0.1", 0, metrics=MetricsRegistry(), output_base_directory=tmp_path),
        driver=ask,
    ), 100.0))
    (late,) = [job for job in seen["view"]["jobs"].values() if job["job_name"] == "wants-three"]
    assert late["status"] == "queued" and late["waiting_on"] == "worker_barrier: wants 3, 2 live"
    assert seen["gauge"] == 1
    assert manager.job_status(job_ids[0])["status"] == "finished"
    assert manager.metrics.gauge("sched_admission_barrier_unmet_job_units", "").value() == 0


# -- a worker that does not answer, its socket up ---------------------------------------------


@pytest.mark.time_limit(120)
def test_a_victim_slow_to_answer_a_preemptions_unqueue_holds_back_no_pass(tmp_path, monkeypatch):
    """A killed process's socket closes when the kernel has torn the process
    down (2 s on the chip machine, where it holds a device): until then the
    worker is live to the master and answers nothing. A pass that awaited a
    preemption's unqueue stood for as long."""
    from tpu_render_cluster.master.worker_handle import WorkerHandle

    # Frames of 0.2 s: when the urgent job arrives both workers render one
    # frame of the long job and hold another queued, so fair share has a
    # frame to ask back at its next pass, and no slot frees by itself first.
    backends = [WritingMock(tmp_path, render_seconds=0.2) for _ in range(2)]
    asked: list[float] = []
    unqueue = WorkerHandle.unqueue_frame

    async def unqueue_slowly(self, job_name, unit):
        asked.append(time.time())
        await asyncio.sleep(1.5)
        return await unqueue(self, job_name, unit)

    monkeypatch.setattr(WorkerHandle, "unqueue_frame", unqueue_slowly)

    async def a_second_job_that_starves(manager, _workers):
        while sum(len(b.rendered_frames) for b in backends) < 4:
            await asyncio.sleep(0.005)
        manager.submit(JobSpec(job=make_job("urgent", 1, 10, None), weight=4.0))
        while not asked and any(run.status != "finished" for run in manager._runs.values()):
            await asyncio.sleep(0.005)

    _traces, job_ids, manager, _workers = asyncio.run(asyncio.wait_for(_run_multi_job(
        [JobSpec(job=make_job("long", 1, 40, None))], backends,
        manager_factory=lambda: JobManager("127.0.0.1", 0, metrics=MetricsRegistry(), output_base_directory=tmp_path),
        driver=a_second_job_that_starves,
    ), 100.0))
    assert asked
    assert all(run.status == "finished" for run in manager._runs.values()) and len(manager._runs) == 2
    passes = manager.metrics.histogram("sched_tick_seconds", "", labels=("phase",)).series(phase="pass")
    assert passes.count > 10 and passes.max < 0.75  # the unqueue took 1.5 s, beside the loop
    renders = Counter((job, frame) for backend in backends for job, frame, _ in backend.began)
    assert len(renders) == 50
