"""Control-plane hot-path suite (PR 17, all tier-1, marked
``schedperf``): the incremental heap WFQ must pick exactly what the
legacy scan picks over randomized event traces, the preserialized
queue-add splice must be byte-identical to ``encode_message`` for every
optional-key combination, the constant-segment cache must invalidate on
job-generation and epoch changes (a stale generation's bytes never
leave the master), each dispatch must serialize exactly once
end-to-end, and a rolling tick-budget overrun must fire the flight
recorder's ``tick_budget`` trigger exactly on the crossing edge.

The randomized equivalence test uses dyadic weights and integer unit
loads so every ``load / weight`` key is exact in binary floating point:
the scan's ``_EPS`` tie tolerance and the heap's total ordering then
agree bit-for-bit, and any pick divergence is a real bug, not a
rounding artifact.
"""

import asyncio
import itertools
import json
import random

import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.obs.registry import MetricsRegistry
from tpu_render_cluster.protocol import frames as pframes
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.protocol.frames import DispatchFrameCache
from tpu_render_cluster.protocol.schema import FRAME_SEGMENTS, WIRE_SCHEMAS
from tpu_render_cluster.sched import fair_share
from tpu_render_cluster.sched.tickprof import TickProfiler
from tpu_render_cluster.sched.wfq import IncrementalWFQ
from tpu_render_cluster.transport.wirecost import BYTES_METRIC, WireAccounting

pytestmark = pytest.mark.schedperf


def make_job(name: str, frames: int = 8, *, start: int = 1) -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description="schedperf test job",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=start,
        frame_range_to=start + frames - 1,
        wait_for_number_of_workers=2,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
    )


# ---------------------------------------------------------------------------
# heap WFQ vs legacy scan: randomized pick-sequence equivalence


class _OracleJob:
    """One job's state of truth for the scan oracle."""

    def __init__(self, job_id, weight, priority):
        self.job_id = job_id
        self.weight = weight
        self.priority = priority
        self.in_flight = 0
        self.pending = 0


def _oracle_inputs(jobs):
    return [
        fair_share.JobShareInput(
            job_id=j.job_id,
            weight=j.weight,
            priority=j.priority,
            in_flight=j.in_flight,
            pending=j.pending,
        )
        for j in jobs.values()
    ]


def _sync_all(wfq, jobs, version):
    # The manager resyncs only DIRTY jobs; here every event dirties at
    # most one job, so resyncing all of them each step additionally
    # proves resync is idempotent for clean entries.
    for j in jobs.values():
        wfq.sync(
            j.job_id,
            weight=j.weight,
            priority=j.priority,
            in_flight=j.in_flight,
            pending=j.pending,
            cost=None,
            state_version=version,
        )


@pytest.mark.parametrize("seed", [1, 7, 40, 1234, 987654])
def test_heap_matches_scan_over_random_event_trace(seed):
    """Drive both structures through a random admit / dispatch /
    complete / fail / reweight / remove trace and demand identical
    dispatch picks and identical preemption decisions at every step."""
    rng = random.Random(seed)
    wfq = IncrementalWFQ()
    jobs: dict[str, _OracleJob] = {}
    version = 0
    admitted = 0
    picks = 0

    for _ in range(600):
        version += 1
        event = rng.random()
        if event < 0.25 or not jobs:
            # Admit: dyadic weight, two priority classes, some backlog.
            admitted += 1
            job = _OracleJob(
                f"job-{admitted:04d}",
                rng.choice((0.5, 1.0, 2.0, 4.0)),
                rng.choice((0, 0, 0, 1)),
            )
            job.pending = rng.randrange(0, 6)
            jobs[job.job_id] = job
        elif event < 0.45:
            # A unit finished (or was evicted back to pending).
            job = jobs[rng.choice(list(jobs))]
            if job.in_flight > 0:
                job.in_flight -= 1
                if rng.random() < 0.3:
                    job.pending += 1  # eviction returns the unit
        elif event < 0.55:
            job = jobs[rng.choice(list(jobs))]
            job.weight = rng.choice((0.5, 1.0, 2.0, 4.0))
        elif event < 0.62:
            job_id = rng.choice(list(jobs))
            del jobs[job_id]
            wfq.remove(job_id)
        else:
            # Backlog arrives (tile split, steal return, resume).
            job = jobs[rng.choice(list(jobs))]
            job.pending += rng.randrange(1, 4)

        _sync_all(wfq, jobs, version)

        # Preemption decision: targets + pick must agree exactly (the
        # inputs are identical up to list order, which both sides build
        # in admission order).
        slots = float(rng.randrange(1, 9))
        oracle_in = _oracle_inputs(jobs)
        heap_in = wfq.inputs()
        assert [i.job_id for i in heap_in] == [i.job_id for i in oracle_in]
        targets = fair_share.compute_slot_targets(oracle_in, slots)
        assert fair_share.pick_preemption(
            heap_in, fair_share.compute_slot_targets(heap_in, slots)
        ) == fair_share.pick_preemption(oracle_in, targets)

        # Drain a few dispatch slots, comparing every pick.
        for _ in range(rng.randrange(0, 4)):
            scan_pick = fair_share.pick_job_to_dispatch(_oracle_inputs(jobs))
            heap_pick = wfq.pick_dispatch()
            assert heap_pick == scan_pick, (
                f"step pick diverged: heap={heap_pick} "
                f"({wfq.key_of(heap_pick) if heap_pick else None}) "
                f"scan={scan_pick} "
                f"({wfq.key_of(scan_pick) if scan_pick else None})"
            )
            if scan_pick is None:
                break
            picks += 1
            job = jobs[scan_pick]
            if rng.random() < 0.1:
                # Dispatch failure: the claimed unit did not land.
                job.pending -= 1
                wfq.on_dispatch_failed(scan_pick)
            else:
                job.pending -= 1
                job.in_flight += 1
                wfq.on_dispatched(scan_pick, 0.0)

    assert picks > 100  # the trace genuinely exercised the dispatch path


def test_heap_tie_breaks_by_admission_order():
    wfq = IncrementalWFQ()
    for job_id in ("b-second", "a-first"):
        wfq.sync(
            job_id, weight=1.0, priority=0, in_flight=0, pending=3,
            cost=None, state_version=1,
        )
    # Equal keys: the job synced FIRST wins, regardless of name order.
    assert wfq.pick_dispatch() == "b-second"


def test_heap_prefers_higher_priority_class():
    wfq = IncrementalWFQ()
    wfq.sync("lo", weight=4.0, priority=0, in_flight=0, pending=5,
             cost=None, state_version=1)
    wfq.sync("hi", weight=0.5, priority=1, in_flight=3, pending=5,
             cost=None, state_version=1)
    assert wfq.pick_dispatch() == "hi"
    wfq.sync("hi", weight=0.5, priority=1, in_flight=3, pending=0,
             cost=None, state_version=2)
    assert wfq.pick_dispatch() == "lo"


def test_heap_cost_metering_changes_pick():
    wfq = IncrementalWFQ()
    # By unit count "slow" looks lighter (1 vs 2); by predicted seconds
    # it is heavier (5.0 vs 0.2) and must lose the pick.
    wfq.sync("slow", weight=1.0, priority=0, in_flight=1, pending=5,
             cost=5.0, state_version=1)
    wfq.sync("fast", weight=1.0, priority=0, in_flight=2, pending=5,
             cost=0.2, state_version=1)
    assert wfq.pick_dispatch() == "fast"
    assert wfq.needs_sync("slow", 1, cost_on=False)  # metering toggle
    assert not wfq.needs_sync("slow", 1, cost_on=True)
    assert wfq.needs_sync("slow", 2, cost_on=True)  # state moved


# ---------------------------------------------------------------------------
# preserialized dispatch frames: byte identity + cache invalidation


def _combo_request(job, trace, job_id, tile, epoch):
    return pm.MasterFrameQueueAddRequest(
        message_request_id=123456789012345678,
        job=job,
        frame_index=42,
        trace=pm.TraceContext(trace_id=2**63 + 5, span_id=7) if trace else None,
        job_id='job-"quoted"é' if job_id else None,
        tile=3 if tile else None,
        epoch=9 if epoch else None,
    )


def test_splice_byte_identical_across_all_optional_combos():
    job = make_job("combo-job")
    cache = DispatchFrameCache()
    for combo in itertools.product((False, True), repeat=4):
        request = _combo_request(job, *combo)
        spliced = cache.encode(request)
        assert spliced == pm.encode_message(request), combo
        # And the wire text round-trips through the ordinary decoder.
        decoded = pm.decode_message(spliced)
        assert decoded.frame_index == 42


def test_constant_segment_cached_within_generation():
    job = make_job("burst-job")
    cache = DispatchFrameCache()
    for frame in range(16):
        request = pm.MasterFrameQueueAddRequest(
            message_request_id=frame + 1, job=job, frame_index=frame,
            trace=None, job_id="burst-job", tile=None, epoch=4,
        )
        assert cache.encode(request) == pm.encode_message(request)
    assert cache.constant_encodes == 1
    assert cache.splices == 16


def test_generation_change_invalidates_cache():
    """A same-name resubmit is a NEW job object — possibly with a
    different spec. The stale generation's bytes must never leave."""
    cache = DispatchFrameCache()
    first = make_job("resub-job", frames=8)
    req = pm.MasterFrameQueueAddRequest(
        message_request_id=1, job=first, frame_index=1,
        trace=None, job_id=None, tile=None, epoch=None,
    )
    cache.encode(req)
    second = make_job("resub-job", frames=20)  # new generation, new spec
    req2 = pm.MasterFrameQueueAddRequest(
        message_request_id=2, job=second, frame_index=1,
        trace=None, job_id=None, tile=None, epoch=None,
    )
    text = cache.encode(req2)
    assert text == pm.encode_message(req2)
    payload = json.loads(text)["payload"]
    assert payload["job"]["frame_range_to"] == second.frame_range_to
    assert cache.constant_encodes == 2


def test_epoch_change_invalidates_cache():
    """A failover bumps the master epoch; a frame spliced after the bump
    must re-encode (the cache key includes the epoch) and carry the new
    epoch — never a predecessor incarnation's."""
    job = make_job("epoch-job")
    cache = DispatchFrameCache()
    for epoch in (1, 1, 2, 2):
        request = pm.MasterFrameQueueAddRequest(
            message_request_id=epoch * 10, job=job, frame_index=1,
            trace=None, job_id=None, tile=None, epoch=epoch,
        )
        text = cache.encode(request)
        assert text == pm.encode_message(request)
        assert json.loads(text)["payload"]["epoch"] == epoch
    assert cache.constant_encodes == 2


def test_cache_capacity_is_bounded():
    cache = DispatchFrameCache()
    for i in range(pframes.CACHE_CAPACITY + 10):
        request = pm.MasterFrameQueueAddRequest(
            message_request_id=i, job=make_job(f"many-{i:03d}"),
            frame_index=1, trace=None, job_id=None, tile=None, epoch=None,
        )
        cache.encode(request)
    assert len(cache._cache) <= pframes.CACHE_CAPACITY


def test_frame_segments_partition_declared_schema():
    for tag, seg in FRAME_SEGMENTS.items():
        schema = WIRE_SCHEMAS[tag]
        constant, varying = set(seg.constant), set(seg.varying)
        assert not constant & varying
        assert constant | varying == set(schema.required) | set(schema.optional)


# ---------------------------------------------------------------------------
# one serialize per message end-to-end


class _FakeConnection:
    last_known_address = "127.0.0.1:0"

    def __init__(self):
        self.sent: list[str] = []

    async def send_text(self, text: str) -> None:
        self.sent.append(text)


def _send_through_handle(monkeypatch, registry):
    """Run one queue-add through WorkerHandle._send_message, counting
    encode_message calls; returns (encode_calls, sent_text)."""
    from tpu_render_cluster.master.worker_handle import WorkerHandle

    connection = _FakeConnection()
    handle = WorkerHandle(1, connection, None, metrics=registry)
    calls = {"n": 0}
    real_encode = pm.encode_message

    def counting_encode(message):
        calls["n"] += 1
        return real_encode(message)

    monkeypatch.setattr(pm, "encode_message", counting_encode)
    request = pm.MasterFrameQueueAddRequest(
        message_request_id=77, job=make_job("count-job"), frame_index=3,
        trace=None, job_id="count-job", tile=None, epoch=None,
    )
    asyncio.run(handle._send_message(request))
    assert len(connection.sent) == 1
    return calls["n"], connection.sent[0]


def test_cached_path_serializes_exactly_once(monkeypatch):
    """The splice path never calls encode_message — not to build the
    frame and (the PR-17 fix) not again inside the wire accounting to
    measure it — yet the accounting still books the exact wire bytes."""
    monkeypatch.setenv("TRC_DISPATCH_FRAMES", "cached")
    registry = MetricsRegistry()
    encode_calls, text = _send_through_handle(monkeypatch, registry)
    assert encode_calls == 0
    series = registry.snapshot()[BYTES_METRIC]["series"]
    booked = sum(
        v for k, v in series.items()
        if "request_frame-queue_add" in k and "send" in k
    )
    assert booked == len(text)


def test_encode_path_serializes_exactly_once(monkeypatch):
    monkeypatch.setenv("TRC_DISPATCH_FRAMES", "encode")
    registry = MetricsRegistry()
    encode_calls, text = _send_through_handle(monkeypatch, registry)
    assert encode_calls == 1
    assert text == pm.encode_message(pm.decode_message(text))


def test_record_send_does_not_reencode(monkeypatch):
    registry = MetricsRegistry()
    wire = WireAccounting(registry)
    calls = {"n": 0}
    real_encode = pm.encode_message

    def counting_encode(message):
        calls["n"] += 1
        return real_encode(message)

    monkeypatch.setattr(pm, "encode_message", counting_encode)
    wire.record_send("request_frame-queue_add", '{"x":1}', 0.001)
    assert calls["n"] == 0
    series = registry.snapshot()[BYTES_METRIC]["series"]
    assert sum(series.values()) == len('{"x":1}')


# ---------------------------------------------------------------------------
# verify tick mode e2e: heap and scan cross-checked on live traffic


@pytest.mark.parametrize("tick_mode", ["scan", "verify"])
def test_tick_modes_complete_multi_job_run(monkeypatch, tick_mode):
    """Both the legacy scan fallback and the verify cross-check (which
    asserts heap-vs-scan pick equality on every live tick) must run two
    overlapping jobs to completion over real sockets."""
    from tpu_render_cluster.harness.local import run_local_multi_job
    from tpu_render_cluster.sched.models import JOB_FINISHED, JobSpec
    from tpu_render_cluster.worker.backends.mock import MockBackend

    monkeypatch.setenv("TRC_SCHED_TICK", tick_mode)
    monkeypatch.setenv("TRC_SCHED_TICK_SECONDS", "0.01")
    specs = [
        JobSpec(job=make_job("mode-a", frames=10), weight=2.0),
        JobSpec(job=make_job("mode-b", frames=10, start=101), weight=1.0),
    ]
    backends = [MockBackend(render_seconds=0.005) for _ in range(2)]
    _traces, job_ids, manager, _workers = run_local_multi_job(
        specs, backends, timeout=120.0
    )
    assert manager.config.tick_mode == tick_mode
    for job_id in job_ids:
        run = manager._runs[job_id]
        assert run.status == JOB_FINISHED
        assert run.state.finished_count() == 10


# ---------------------------------------------------------------------------
# tick-budget flight trigger: edge-fired, re-armed on recovery


class _FakeFlightRecorder:
    def __init__(self):
        self.fired: list[tuple[str, dict]] = []

    def trigger(self, kind, detail=None):
        self.fired.append((kind, detail or {}))


def test_tick_budget_trigger_fires_on_crossing_edge():
    from tpu_render_cluster.obs.flightrec import TRIGGER_TICK_BUDGET

    recorder = _FakeFlightRecorder()
    registry = MetricsRegistry()
    # A budget so small every real tick overruns it.
    profiler = TickProfiler(
        registry, None, tick_budget_seconds=1e-9, flightrec=recorder
    )
    for _ in range(3):
        profiler.begin_tick()
        profiler.end_tick()
    # Sustained overrun: ONE dump at the crossing, not one per tick.
    assert [kind for kind, _ in recorder.fired] == [TRIGGER_TICK_BUDGET]
    detail = recorder.fired[0][1]
    assert detail["budget_ratio"] > 1.0
    assert detail["ticks"] == 1

    # Recovery (a huge budget drops the rolling ratio under 1) re-arms...
    profiler.tick_budget_seconds = 1e9
    profiler.begin_tick()
    profiler.end_tick()
    assert len(recorder.fired) == 1
    # ...so the next overrun fires a second dump.
    profiler.tick_budget_seconds = 1e-9
    profiler.begin_tick()
    profiler.end_tick()
    assert [kind for kind, _ in recorder.fired] == [TRIGGER_TICK_BUDGET] * 2


# --- dashboard: the before/after control-plane A/B rows ----------------------


def test_dashboard_renders_sched_bench_rows():
    """The "where did the time go" panel shows before/after assignments/s
    and the share_scan p99 per tick mode, sourced from a SCHED_BENCH.json
    record, plus the headline speedup at the measured concurrency."""
    from tpu_render_cluster.obs.dashboard import render_dashboard

    record = {
        "jobs": 64,
        "scan": {
            "tick_mode": "scan + per-send encode",
            "assignments_per_s": 80.3,
            "share_scan_p99_s": 0.0206,
        },
        "heap": {
            "tick_mode": "heap + preserialized frames",
            "assignments_per_s": 160.0,
            "share_scan_p99_s": 0.00036,
        },
        "speedup_assignments_per_s": 1.993,
    }
    frame = render_dashboard({}, {}, sched_bench=record)
    assert "sched A/B (SCHED_BENCH.json)" in frame
    assert "scan + per-send encode" in frame
    assert "heap + preserialized frames" in frame
    assert "80.3" in frame and "160.0" in frame
    assert "speedup 1.99x @ 64 concurrent jobs" in frame
    # Without a record the panel simply isn't there — no placeholder rows.
    assert "sched A/B" not in render_dashboard({}, {})


def test_load_sched_bench_handles_missing_and_committed(tmp_path):
    from tpu_render_cluster.obs.dashboard import load_sched_bench

    assert load_sched_bench(str(tmp_path / "absent.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("not json", encoding="utf-8")
    assert load_sched_bench(str(bad)) is None
    # The committed artifact loads through the default
    # path and carries both modes.
    record = load_sched_bench()
    assert record is not None
    assert record["scan"]["assignments_per_s"] > 0
    assert record["heap"]["assignments_per_s"] > 0
