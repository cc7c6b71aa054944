"""The service on a pool: one ``JobManager`` feeding FOUR workers.

- two families through ``run_local_multi_job`` on four ``tpu-raytrace``
  workers at 64x64: per job the files ``run-job`` writes, bit for bit, the
  tree ``benchmark/reference/plain_service.py`` expects, every (job, frame)
  rendered once by the workers' own timelines
  (``benchmark/reference/plain_pool.py``), and the pool's spans and counters
  with the values the run implies;
- one worker's preparation held open: it gets no frame of that job before
  it reports ready while the other three go on with it;
- a worker that answers its queue-adds slowly holds back its own frames and
  nobody else's;
- a unit taken back while a worker has it in hand is rendered twice, counted
  with its cause and explained by ``plain_pool``; a hand-made duplicate is not;
- ``status`` after 200 ended jobs lists the newest and answers for all.
"""

from __future__ import annotations

import asyncio
import json
import time

import pytest

from benchmark.reference import plain_pool, plain_service
from tests.test_service import FAMILY_JOBS, SlowToPrepare, backend_at, make_job
from tpu_render_cluster.harness.local import _run_multi_job, run_local_job, run_local_multi_job
from tpu_render_cluster.master.state import FrameStatus
from tpu_render_cluster.obs import MetricsRegistry, validate_trace_document
from tpu_render_cluster.sched import manager as manager_module
from tpu_render_cluster.sched.control import handle_request
from tpu_render_cluster.sched.manager import JobManager
from tpu_render_cluster.sched.models import JobSpec
from tpu_render_cluster.worker.backends.mock import MockBackend

POOL = 4
POOL_JOBS = {  # family -> (job name, first, last, shape): more frames than test_service's, to spread
    "04_very-simple": ("04_very-simple_svc-0001", 1, 8, FAMILY_JOBS["04_very-simple"][3]),
    "03_physics-2-mesh": ("03_physics-2-mesh_svc-0002", 288, 291, FAMILY_JOBS["03_physics-2-mesh"][3]),
}


def rendered_by_worker(workers, directory) -> dict[str, list[tuple[str, int]]]:
    """Each worker's own record, through the file it would export."""
    out = {}
    for index, worker in enumerate(workers):
        path = worker.span_tracer.export(directory / f"worker-{index}_trace-events.json")
        out[path.name] = plain_pool.rendered_units(path)
    return out


def reports_of(manager) -> list[dict]:
    return asyncio.run(handle_request(manager, {"op": "handbacks"}))["handbacks"]


# -- two families on four workers ----------------------------------------------------


@pytest.fixture(scope="module")
def pool_served(tmp_path_factory):
    from tpu_render_cluster import obs
    from tpu_render_cluster.obs.startup import reset_startup

    base = tmp_path_factory.mktemp("pool_served")
    previous, obs._global_registry = obs._global_registry, obs.MetricsRegistry()
    reset_startup()
    jobs = {family: make_job(name, first, last, shape) for family, (name, first, last, shape) in POOL_JOBS.items()}
    try:
        _traces, job_ids, manager, workers = run_local_multi_job(
            [JobSpec(job=job) for job in jobs.values()], [backend_at(base, 8) for _ in range(POOL)], timeout=280.0,
        )
        yield {
            "base": base, "jobs": jobs, "manager": manager, "job_ids": job_ids, "workers": workers,
            "rendered": rendered_by_worker(workers, tmp_path_factory.mktemp("timelines")),
        }
    finally:
        obs._global_registry = previous


@pytest.mark.parametrize("family", list(POOL_JOBS))
def test_a_job_served_by_four_workers_has_run_jobs_files_bit_for_bit(pool_served, family, tmp_path):
    job = pool_served["jobs"][family]
    run_local_job(job, [backend_at(tmp_path, 3)], timeout=280.0)  # the same job alone, through run-job
    names = sorted(p.name for p in (pool_served["base"] / "frames" / job.job_name).iterdir())
    assert names == [f"rendered-{frame:06d}.jpg" for frame in job.frame_indices()]
    for name in names:
        together = (pool_served["base"] / "frames" / job.job_name / name).read_bytes()
        assert together == (tmp_path / "frames" / job.job_name / name).read_bytes(), name


def test_the_pools_tree_is_what_plain_service_expects(pool_served):
    described = [
        {"name": job.job_name, "directory": job.job_name, "first": job.frame_range_from, "last": job.frame_range_to,
         "name_format": job.output_file_name_format, "file_format": job.output_file_format,
         "width": POOL_JOBS[family][3]["width"], "height": POOL_JOBS[family][3]["height"]}
        for family, job in pool_served["jobs"].items()
    ]
    statuses = [pool_served["manager"].job_status(job_id)["status"] for job_id in pool_served["job_ids"]]
    assert statuses == ["finished", "finished"]
    must, may = plain_service.expected(described, {job["name"] for job in described})
    assert len(must) == 12 and not may
    assert plain_service.compare(pool_served["base"] / "frames", must, may) == []


def test_every_unit_of_the_pool_was_rendered_once_by_the_workers_own_record(pool_served):
    rendered = pool_served["rendered"]
    assert len(rendered) == POOL and all(units is not None for units in rendered.values())
    units = [unit for worker in rendered.values() for unit in worker]
    assert sorted(units) == sorted(
        (job.job_name, frame) for job in pool_served["jobs"].values() for frame in job.frame_indices()
    )
    assert plain_pool.account(rendered, reports_of(pool_served["manager"])) == ([], [])
    # the jobs were spread: no worker rendered everything
    assert max(len(worker) for worker in rendered.values()) < len(units)


def test_the_pools_spans_and_counters_say_what_the_run_did(pool_served):
    manager = pool_served["manager"]
    events = manager.span_tracer.events()
    assert validate_trace_document(manager.span_tracer.to_chrome()) == []
    all_ready = []
    for job_id in pool_served["job_ids"]:
        whole = [e for e in events if e["name"] == "job_announce" and e["args"]["job_id"] == job_id]
        children = [e for e in events if e["name"] == "announce worker" and e["args"]["job_id"] == job_id]
        assert len(whole) == 1 and whole[0]["args"]["announced"] == POOL
        # On a busy host a job of a dozen 64x64 frames can end on the workers that were
        # ready first, before the last has prepared it: the span then says so.
        ready = whole[0]["args"]["ready"]
        all_ready.append(whole[0]["args"]["all_ready"])
        assert 1 <= ready <= POOL and all_ready[-1] is (ready == POOL)
        assert len(children) == ready and len({e["args"]["worker"] for e in children}) == ready
        assert {e["tid"] for e in children} == {whole[0]["tid"]}  # on the job's own track
        if all_ready[-1]:  # from admission to the LAST worker's ready event
            assert whole[0]["ts"] + whole[0]["dur"] == pytest.approx(max(e["ts"] + e["dur"] for e in children), abs=2000)
        assert all(e["ts"] >= whole[0]["ts"] - 1 for e in children)
        assert all(e["ts"] + e["dur"] <= whole[0]["ts"] + whole[0]["dur"] + 2000 for e in children)
    announce = manager.metrics.histogram("sched_job_announce_seconds", "", labels=("edge",))
    assert announce.series(edge="first_ready").count == 2 and announce.series(edge="all_ready").count == sum(all_ready)
    if all(all_ready):
        assert announce.series(edge="first_ready").sum <= announce.series(edge="all_ready").sum
    spread = manager.metrics.histogram("sched_job_worker_units", "", buckets=manager_module.JOB_WORKERS_BUCKETS).series()
    by_job = [len({w for w, units in pool_served["rendered"].items() if any(u[0] == job.job_name for u in units)})
              for job in pool_served["jobs"].values()]
    assert spread.count == 2 and spread.sum == sum(by_job) and max(by_job) > 1
    twice = manager.metrics.counter("sched_units_rendered_twice_total", "", labels=("cause",))
    assert twice.value(cause="none") == 0
    assert 0 < manager.metrics.counter("master_process_cpu_seconds_total", "").value() <= time.process_time()
    from tpu_render_cluster.obs.prometheus import render_prometheus

    exposition = render_prometheus(manager.metrics.snapshot())
    for series in ('sched_units_rendered_twice_total{cause="none"} 0', "master_process_cpu_seconds_total ",
                   'sched_job_announce_seconds_count{edge="first_ready"} 2', "sched_job_worker_units_count 2",
                   *([f'sched_job_announce_seconds_count{{edge="all_ready"}} {sum(all_ready)}'] if any(all_ready) else [])):
        assert series in exposition, series


# -- one worker's preparation held open ----------------------------------------------


def test_a_worker_still_preparing_a_job_gets_none_of_its_frames_while_the_others_go_on():
    slow = SlowToPrepare(0.8)
    others = [SlowToPrepare(0.0) for _ in range(POOL - 1)]
    # 300 frames of 20 ms on three workers outlast the fourth's preparation
    specs = [JobSpec(job=make_job("slow-shot", 1, 300, None)), JobSpec(job=make_job("quick-preview", 1, 60, None))]
    _traces, job_ids, manager, _workers = run_local_multi_job(specs, [slow, *others], timeout=120.0)
    assert all(manager.job_status(job_id)["status"] == "finished" for job_id in job_ids)
    ready = slow.ready_at["slow-shot"]
    on_slow = [at for name, at in slow.rendered_at if name == "slow-shot"]
    elsewhere = [at for backend in others for name, at in backend.rendered_at if name == "slow-shot"]
    assert len(on_slow) + len(elsewhere) == 300 and on_slow
    assert all(at >= ready for at in on_slow)
    # the other three went on with the job under the fourth's preparation
    assert sum(1 for at in elsewhere if at < ready) >= 10
    # and the fourth went on with the job it had
    assert sum(1 for name, at in slow.rendered_at if name == "quick-preview" and at < ready) >= 3
    announce = manager.metrics.histogram("sched_job_announce_seconds", "", labels=("edge",))
    assert announce.series(edge="first_ready").sum < 0.8 <= announce.series(edge="all_ready").sum


# -- a worker slow to acknowledge ------------------------------------------------------


def test_a_worker_slow_to_take_its_queue_adds_holds_back_nobody_else():
    frames, slow_id = 240, {}
    backends = [MockBackend(render_seconds=0.02, load_seconds=0.0, save_seconds=0.0) for _ in range(POOL)]

    async def note_the_slow_one(_manager, workers, _tasks):
        while workers[0].worker_id is None:
            await asyncio.sleep(0.01)
        slow_id["id"] = workers[0].worker_id

    def delay(worker_id: int, _frame: int) -> float:
        return 0.25 if worker_id == slow_id.get("id") else 0.0

    async def run():
        return await _run_multi_job(
            [JobSpec(job=make_job("backlog", 1, frames, None))], backends,
            manager_factory=lambda: JobManager("127.0.0.1", 0, metrics=MetricsRegistry(), dispatch_delay_fn=delay),
            on_cluster_started=note_the_slow_one,
        )

    started = time.time()
    _traces, job_ids, manager, _workers = asyncio.run(asyncio.wait_for(run(), 120.0))
    elapsed = time.time() - started
    assert manager.job_status(job_ids[0])["status"] == "finished"
    by_worker = [len(backend.rendered_frames) for backend in backends]
    assert sum(by_worker) == frames
    # Every queue-add to the slow worker takes a quarter of a second. A pass
    # that waited for them gave the other three 6 frames for its 2 (a
    # quarter of the job to the slow one, half a minute in all); now they
    # render at their own pace and it gets what it can take.
    assert by_worker[0] <= frames // 8, by_worker
    assert min(by_worker[1:]) >= frames // 5, by_worker
    assert elapsed < 15.0


# -- a unit rendered twice, with and without a cause ------------------------------------


def test_a_unit_taken_back_in_hand_is_rendered_twice_with_its_cause_and_a_made_up_pair_has_none(tmp_path):
    backends = [MockBackend(render_seconds=0.15) for _ in range(2)]
    taken = {}

    async def take_back_a_unit_in_hand(manager, _workers):
        """What a preemption whose answer crossed the frame's start comes
        to: the unit goes back to its pool while a worker renders it, and
        the other worker is handed it."""
        while not taken:
            await asyncio.sleep(0.005)
            for run in manager._runs.values():
                if run.state is None:
                    continue
                for unit, record in run.state.frames.items():
                    if record.status is FrameStatus.RENDERING_ON_WORKER:
                        (other,) = [w for w in manager.live_workers() if w.worker_id != record.worker_id]
                        run.state.return_frame_to_pending(unit, "preemption")
                        await other.queue_frame(run.spec.job, unit, job_id=run.job_id)
                        taken["unit"] = (run.job_name, unit.frame_index)
                        return

    _traces, job_ids, manager, workers = run_local_multi_job(
        [JobSpec(job=make_job("shot", 1, 12, None))], backends, timeout=60.0, driver=take_back_a_unit_in_hand,
    )
    assert manager.job_status(job_ids[0])["status"] == "finished"
    rendered = rendered_by_worker(workers, tmp_path)
    reports = reports_of(manager)
    assert [(r["job_name"], r["frame"], r["cause"]) for r in reports if r["cause"] == "preemption"] == [
        (*taken["unit"], "preemption")
    ]
    explained, unexplained = plain_pool.account(rendered, reports)
    assert unexplained == []
    assert [(e["job"], e["frame"], e["renders"], e["causes"]) for e in explained] == [(*taken["unit"], 2, ["preemption"])]
    twice = manager.metrics.counter("sched_units_rendered_twice_total", "", labels=("cause",))
    assert twice.value(cause="preemption") == 1 and twice.value(cause="none") == 0
    assert manager.job_status(job_ids[0])["ledger"]["duplicate_results"] == 1
    # a pair nobody accounts for: the same record, and one more render of frame 9 by hand
    first = sorted(rendered)[0]
    forged = {**rendered, first: rendered[first] + [("shot", 9)]}
    explained, unexplained = plain_pool.account(forged, reports)
    assert [(e["job"], e["frame"], e["renders"], e["causes"]) for e in unexplained] == [("shot", 9, 2, [])]
    assert len(explained) == 1


@pytest.mark.parametrize("spans, want", [
    ([{"ph": "X", "cat": "worker", "name": "render", "args": {"frame": 3, "job": "a"}},
      {"ph": "X", "cat": "worker", "name": "write", "args": {"frame": 3, "job": "a"}},
      {"ph": "X", "cat": "worker.step", "name": "encode", "args": {"frame": 3}}], [("a", 3)]),
    ([{"ph": "X", "cat": "worker", "name": "render", "args": {"frame": 3}}], None),  # a program that names no job
    ([], []),
])
def test_plain_pool_reads_a_workers_record_from_its_render_spans(tmp_path, spans, want):
    path = tmp_path / "worker_trace-events.json"
    path.write_text(json.dumps({"traceEvents": spans}))
    assert plain_pool.rendered_units(path) == want


@pytest.mark.parametrize("reported, explained, unexplained", [
    ([], 0, 2),
    ([{"job_name": "a", "frame": 1, "cause": "eviction"}], 1, 1),
    ([{"job_name": "a", "frame": 1, "cause": "eviction"}, {"job_name": "b", "frame": 1, "cause": "steal"}], 1, 1),
    ([{"job_name": "a", "frame": 1, "cause": "eviction"}, {"job_name": "a", "frame": 2, "cause": "preemption"},
      {"job_name": "a", "frame": 2, "cause": "preemption"}], 2, 0),
])
def test_plain_pool_wants_a_stated_cause_for_every_render_but_one(reported, explained, unexplained):
    rendered = {"w0": [("a", 1), ("a", 2), ("b", 1)], "w1": [("a", 1), ("a", 2)], "w2": [("a", 2), ("a", 3)]}
    got = plain_pool.account(rendered, reported)
    assert (len(got[0]), len(got[1])) == (explained, unexplained)
    assert {(e["job"], e["frame"]) for e in got[0] + got[1]} == {("a", 1), ("a", 2)}


# -- status on a service that has run for a while -----------------------------------------


@pytest.fixture(scope="module")
def after_200_jobs():
    listed = manager_module.ENDED_JOBS_LISTED
    manager_module.ENDED_JOBS_LISTED = 50
    answers = {}

    async def ask(manager, _workers):
        while sum(run.final_view is not None for run in manager._runs.values()) < 200:
            await asyncio.sleep(0.02)
        started = time.perf_counter()
        for _ in range(20):
            answers["all"] = await handle_request(manager, {"op": "status"})
        answers["seconds_a_call"] = (time.perf_counter() - started) / 20
        answers["again"] = await handle_request(manager, {"op": "status"})
        answers["first"] = await handle_request(manager, {"op": "status", "job_id": "job-0001"})
        answers["unknown"] = await handle_request(manager, {"op": "status", "job_id": "job-9999"})

    try:
        specs = [JobSpec(job=make_job(f"tiny-{index:03d}", 1, 2, None)) for index in range(200)]
        backends = [MockBackend(render_seconds=0.002, load_seconds=0.0, save_seconds=0.0) for _ in range(POOL)]
        _traces, job_ids, manager, _workers = run_local_multi_job(specs, backends, timeout=240.0, driver=ask)
        yield {"answers": answers, "job_ids": job_ids, "manager": manager}
    finally:
        manager_module.ENDED_JOBS_LISTED = listed


def test_status_without_a_job_lists_the_newest_ended_jobs_and_what_is_live(after_200_jobs):
    answer = after_200_jobs["answers"]["all"]
    assert answer["ok"] and not answer["sched"]["running"] and not answer["sched"]["admission_queue"]
    listed = list(answer["sched"]["jobs"])
    assert listed == after_200_jobs["job_ids"][-50:]  # the newest 50, in submit order
    assert all(view["status"] == "finished" and view["frames_finished"] == 2 for view in answer["sched"]["jobs"].values())
    # what the list costs: an ended job's view is the dict frozen as it ended, not made again
    again = after_200_jobs["answers"]["again"]["sched"]["jobs"]
    assert all(again[job_id] is answer["sched"]["jobs"][job_id] for job_id in listed)
    assert len(json.dumps(answer)) < 60_000 and after_200_jobs["answers"]["seconds_a_call"] < 0.01


def test_status_with_a_job_id_answers_for_every_job_the_service_has_had(after_200_jobs):
    first = after_200_jobs["answers"]["first"]
    assert first["ok"] and first["job"]["job_name"] == "tiny-000" and first["job"]["status"] == "finished"
    assert first["job"]["ledger"]["ok_results"] == 2 and first["job"]["makespan_seconds"] > 0
    assert after_200_jobs["answers"]["unknown"] == {"ok": False, "error": "unknown job_id: 'job-9999'"}
    manager = after_200_jobs["manager"]
    assert manager.metrics.counter("sched_jobs_finished_total", "").value() == 200
    assert len(manager.scheduler_view()["jobs"]) == 50 and len(manager._runs) == 200


# -- what the master reports of units taken back is bounded -----------------------------------


def test_a_jobs_handbacks_are_reported_while_it_is_listed_and_dropped_when_it_is_not(monkeypatch):
    """Six two-frame jobs one after another with three ended jobs listed; the
    first frame of each is taken back once by hand as it is queued."""
    monkeypatch.setattr(manager_module, "ENDED_JOBS_LISTED", 3)
    seen = {"since": [], "taken": set()}

    async def take_back_and_ask(manager, _workers):
        newest = None
        while sum(run.final_view is not None for run in manager._runs.values()) < 6:
            await asyncio.sleep(0.005)
            for run in manager._runs.values():
                if run.state is None or run.job_id in seen["taken"]:
                    continue
                for unit, record in run.state.frames.items():
                    if record.status is FrameStatus.RENDERING_ON_WORKER:
                        seen["taken"].add(run.job_id)
                        run.state.return_frame_to_pending(unit, "drain")
                        break
            answer = await handle_request(manager, {"op": "handbacks", "since": newest})
            seen["since"] += answer["handbacks"]
            if answer["handbacks"]:
                newest = answer["handbacks"][-1]["at"]

    specs = [JobSpec(job=make_job(f"short-{index}", 1, 2, None)) for index in range(6)]
    backends = [MockBackend(render_seconds=0.05, load_seconds=0.0, save_seconds=0.0)]
    _traces, job_ids, manager, _workers = run_local_multi_job(specs, backends, timeout=60.0, driver=take_back_and_ask)
    assert [manager.job_status(job_id)["status"] for job_id in job_ids] == ["finished"] * 6
    # asked as it went, with the newest time it had: every job's, each once
    assert sorted(r["job_id"] for r in seen["since"]) == sorted(job_ids)
    assert [r["at"] for r in seen["since"]] == sorted(r["at"] for r in seen["since"])
    # asked at the end: the three listed jobs', and the states of the others hold none
    assert {r["job_id"] for r in reports_of(manager)} == set(job_ids[-3:]) == set(manager._ended)
    assert [len(manager._runs[job_id].state.handbacks) for job_id in job_ids] == [0, 0, 0, 1, 1, 1]
    assert asyncio.run(handle_request(manager, {"op": "handbacks", "since": time.time()})) == {"ok": True, "handbacks": []}
    assert asyncio.run(handle_request(manager, {"op": "handbacks", "since": "yesterday"}))["ok"] is False


@pytest.mark.parametrize("cause", [None, "requeue", "because"])
def test_a_unit_goes_back_to_its_pool_only_for_a_cause_the_master_can_report(cause):
    from tpu_render_cluster.master.state import HANDBACK_CAUSES, ClusterManagerState

    state = ClusterManagerState(make_job("shot", 1, 2, None))
    state.mark_frame_as_queued(1, 7, time.time())
    with pytest.raises((TypeError, ValueError)):
        state.return_frame_to_pending(1) if cause is None else state.return_frame_to_pending(1, cause)
    assert state.handbacks == [] and state.frames[next(iter(state.frames))].status is FrameStatus.QUEUED_ON_WORKER
    assert set(HANDBACK_CAUSES) == plain_pool.CAUSES  # the guarantee's own list, stated twice and the same


def test_plain_pool_takes_no_report_without_one_of_the_guarantees_causes_for_a_cause():
    rendered = {"w0": [("a", 1)], "w1": [("a", 1)]}
    for cause in ("requeue", "", "none"):
        explained, unexplained = plain_pool.account(rendered, [{"job_name": "a", "frame": 1, "cause": cause}])
        assert explained == [] and [u["causes"] for u in unexplained] == [[]]
    for cause in sorted(plain_pool.CAUSES):
        explained, unexplained = plain_pool.account(rendered, [{"job_name": "a", "frame": 1, "cause": cause}])
        assert unexplained == [] and [e["causes"] for e in explained] == [[cause]]


# -- a frame counts to the pass that claimed it -------------------------------------------------


def test_a_frame_counts_to_the_kind_of_pass_that_claimed_it_though_its_queue_add_ends_in_a_later_one():
    """Every queue-add takes three ticks to be acknowledged, so the loop has
    ticked on by then; the frames still count as the claiming pass's kind."""
    from collections import Counter

    from tpu_render_cluster.sched.manager import SchedulerConfig

    claimed, landed_in = Counter(), Counter()

    class Recording(JobManager):
        async def _send_claims(self, worker, claims, trigger):
            claimed[trigger] += len(claims)
            await super()._send_claims(worker, claims, trigger)
            landed_in[self.dispatch_wakeup.trigger] += len(claims)

    async def run():
        return await _run_multi_job(
            [JobSpec(job=make_job("shot", 1, 24, None))],
            [MockBackend(render_seconds=0.01, load_seconds=0.0, save_seconds=0.0) for _ in range(2)],
            manager_factory=lambda: Recording(
                "127.0.0.1", 0, metrics=MetricsRegistry(), dispatch_delay_fn=lambda _worker, _frame: 0.06,
                config=SchedulerConfig(tick_seconds=0.02),
            ),
        )

    _traces, job_ids, manager, _workers = asyncio.run(asyncio.wait_for(run(), 60.0))
    assert manager.job_status(job_ids[0])["status"] == "finished"
    counted = manager.metrics.counter("master_dispatch_frames_total", "", labels=("trigger",))
    assert sum(claimed.values()) == 24 and claimed["event"] > 0
    assert {kind: counted.value(trigger=kind) for kind in ("event", "tick")} == {
        "event": claimed["event"], "tick": claimed["tick"]
    }
    # the passes the acknowledgements landed in were of another mix: ticks, with nothing to do
    assert landed_in["tick"] > claimed["tick"]
