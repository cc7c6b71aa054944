"""Whole-stack time-attribution suite (sched/tickprof, obs/loopmon,
transport/wirecost, analysis/attribution).

All tier-1 (marked ``attrib``):

- partition math: the five-way carve sums to 1.0 by construction,
  clamps overlapping instrumentation, honors the explicit
  worker-seconds fallback, and returns None with no denominator;
- wire costs: a real 2-worker harness run where the master's per-tag
  send byte counters agree EXACTLY with the workers' recv counters (and
  vice versa) — the codec wrapper adds nothing to the wire, so both
  ends count the same UTF-8 text — plus the top-talkers fold;
- tick profiler: per-phase sums bounded by the tick total, the budget
  gauge, spans on the dedicated "sched" track passing the validator's
  attribution-track invariant, and the ``TRC_SCHED_PROFILE=0`` no-op;
- loop monitor: a deliberately-blocked loop is detected (histogram +
  blocked-episode counter), spans the "loop" track, and fires the
  flight recorder's ``loop_lag`` trigger;
- the acceptance e2e: mid-job ``/metrics`` scrapes on BOTH the master
  (scheduler service) and a worker endpoint show populated
  ``sched_tick_seconds{phase}`` / ``obs_loop_lag_seconds`` /
  ``transport_message_bytes_total{tag}`` series, and the post-run
  statistics.json-shaped fold carries an ``attribution`` section whose
  fractions sum to 1.0 +- 0.05;
- dashboard: the "where did the time go" panel renders, and degenerate
  (empty / +Inf-only) histograms never raise or print "inf".
"""

from __future__ import annotations

import asyncio
import math
import time
import urllib.request

import pytest

from tpu_render_cluster.analysis.attribution import (
    FRACTION_KEYS,
    attribution_report,
)
from tpu_render_cluster.analysis.obs_events import summarize_attribution
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.obs import FlightRecorder, MetricsRegistry, Tracer
from tpu_render_cluster.obs.dashboard import render_dashboard
from tpu_render_cluster.obs.loopmon import (
    BLOCKED_CAUSES,
    BLOCKED_SECONDS_METRIC,
    EPISODES_METRIC,
    LAG_METRIC,
    LoopLagMonitor,
    blocked_cause,
    run_delay_seconds,
)
from tpu_render_cluster.obs.validate import validate_trace_document
from tpu_render_cluster.sched.tickprof import (
    LOOP_PHASES,
    TICK_METRIC,
    TickProfiler,
    observe_dispatch_phase,
)
from tpu_render_cluster.transport.wirecost import (
    BYTES_METRIC,
    SERIALIZE_METRIC,
    WireAccounting,
    top_talkers,
)

pytestmark = pytest.mark.attrib


def _job(name: str, frames: int, workers: int = 2) -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description="attribution suite job",
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


def _fetch(port: int, path: str) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as response:
        return response.read().decode("utf-8")


def _tag_bytes(snapshot: dict, direction: str) -> dict[str, float]:
    """Per-tag byte totals for one direction from a registry snapshot."""
    out: dict[str, float] = {}
    entry = snapshot.get(BYTES_METRIC) or {}
    for key, value in (entry.get("series") or {}).items():
        labels = dict(
            part.partition("=")[::2] for part in key.split(",")
        )
        if labels.get("direction") == direction:
            tag = labels.get("tag", "?")
            out[tag] = out.get(tag, 0.0) + value
    return out


# ---------------------------------------------------------------------------
# Partition math


def test_attribution_partition_sums_to_one_and_clamps():
    sections = {
        "run": {
            "workers": {
                "w1": {"busy_s": 6.0, "idle_s": 2.0},
                "w2": {"busy_s": 4.0, "idle_s": 4.0},
            }
        }
    }
    report = attribution_report(
        critical_sections=sections,
        device_seconds=20.0,  # over-reported: must clamp to busy (10)
        transport_seconds=1.0,
        control_seconds=2.0,
    )
    assert report is not None
    assert report["worker_seconds"] == 16.0
    seconds = report["seconds"]
    assert seconds["device_compute"] == 10.0  # clamped to the busy pool
    assert seconds["transport"] == 1.0
    assert seconds["control_plane"] == 2.0
    assert seconds["queue_wait"] == 3.0  # residual, capped by idle (6)
    assert seconds["host_glue"] == 0.0
    assert set(report["fractions"]) == set(FRACTION_KEYS)
    assert abs(report["fractions_sum"] - 1.0) < 1e-9
    assert all(0.0 <= report["fractions"][k] <= 1.0 for k in FRACTION_KEYS)
    # Per-run apportioning exists and each run's carve also sums to 1.
    per_run = report["per_run"]
    assert abs(sum(per_run["run"]["fractions"].values()) - 1.0) < 5e-6


def test_attribution_report_worker_seconds_fallback_and_empty():
    report = attribution_report(
        worker_seconds=10.0, device_seconds=4.0, transport_seconds=1.0
    )
    assert report is not None
    assert report["worker_seconds"] == 10.0
    assert report["seconds"]["device_compute"] == 4.0
    assert abs(report["fractions_sum"] - 1.0) < 1e-9
    # No critical sections AND no explicit window -> no denominator.
    assert attribution_report() is None
    assert attribution_report(worker_seconds=0.0) is None


def test_summarize_attribution_takes_device_wait_as_its_device_seconds():
    """The device's share is the measured step: the sum of
    ``worker_frame_step_seconds{step="device_wait"}`` over the snapshots'
    registries (and the heartbeat wire form of a file that has none), no
    other step and no other series."""
    from tpu_render_cluster.analysis.obs_events import summarize_attribution

    def steps(device_wait, readback):
        return {
            "step=device_wait": {"count": 4, "sum": device_wait},
            "step=readback": {"count": 4, "sum": readback},
        }

    tick = {"series": {"phase=total": {"count": 2, "sum": 0.5}}}
    snapshots = [
        {
            "written_at": 1.0,
            "metrics": {"sched_tick_seconds": tick},
            "workers": {
                "w0": {"worker_frame_step_seconds": {"series": steps(3.0, 7.0)}},
                "w1": {"worker_frame_step_seconds": {"series": steps(1.5, 9.0)}},
            },
        },
        {  # a master's file: only the merged heartbeat wire form
            "written_at": 2.0,
            "cluster_metrics": {
                "h": {
                    "worker_frame_step_seconds|step=device_wait": {"n": 2, "s": 0.25},
                    "worker_frame_step_seconds|step=encode": {"n": 2, "s": 5.0},
                }
            },
        },
    ]
    report = summarize_attribution(snapshots, worker_seconds=100.0)
    assert report["seconds"]["device_compute"] == pytest.approx(3.0 + 1.5 + 0.25)
    assert report["seconds"]["control_plane"] == pytest.approx(0.5)
    # a run that rendered on no device carves nothing out for it
    no_device = summarize_attribution(
        [{"written_at": 1.0, "metrics": {"sched_tick_seconds": tick}}],
        worker_seconds=100.0,
    )
    assert no_device["seconds"]["device_compute"] == 0.0


# ---------------------------------------------------------------------------
# Wire-cost accounting


def test_wire_accounting_counts_exact_bytes_and_passthrough():
    from tpu_render_cluster.protocol import messages as pm

    registry = MetricsRegistry()
    wire = WireAccounting(registry)
    message = pm.MasterHandshakeRequest(server_version="1.0.0")
    text = wire.encode(message)
    assert text == pm.encode_message(message)  # identical wire bytes
    decoded = wire.decode(text)
    assert isinstance(decoded, pm.MasterHandshakeRequest)
    snapshot = registry.snapshot()
    sent = _tag_bytes(snapshot, "send")
    received = _tag_bytes(snapshot, "recv")
    assert sent[message.type_name] == len(text) == len(text.encode("utf-8"))
    assert received[message.type_name] == len(text)
    serialize = snapshot[SERIALIZE_METRIC]["series"]
    assert sum(s["count"] for s in serialize.values()) == 2
    # metrics=None is the bare codec.
    bare = WireAccounting(None)
    assert bare.encode(message) == text
    assert isinstance(bare.decode(text), pm.MasterHandshakeRequest)


def test_top_talkers_fold_orders_by_bytes():
    registry = MetricsRegistry()
    wire = WireAccounting(registry)
    from tpu_render_cluster.protocol import messages as pm

    small = pm.MasterHandshakeRequest(server_version="1")
    big = pm.MasterFrameQueueAddRequest(
        message_request_id=1, job=_job("talkers", 4), frame_index=2
    )
    for _ in range(3):
        wire.encode(big)
    wire.encode(small)
    rows = top_talkers(registry.snapshot(), limit=5)
    assert rows[0]["tag"] == big.type_name
    assert rows[0]["bytes"] > rows[-1]["bytes"]
    assert rows[0]["send_bytes"] == rows[0]["bytes"]
    assert rows[0]["serialize_s"] >= 0.0
    assert len(top_talkers(registry.snapshot(), limit=1)) == 1
    assert top_talkers({}) == []


def test_wire_both_ends_agree_over_real_sockets():
    """The per-tag send counters on one socket end equal the recv
    counters on the other, exactly, over a real 2-worker run — the
    accounting observes the same UTF-8 text both ends already exchange,
    so any disagreement means bytes were invented or lost."""
    from tpu_render_cluster.harness.local import _run
    from tpu_render_cluster.worker.backends.mock import MockBackend

    backends = [MockBackend(render_seconds=0.02) for _ in range(2)]

    async def scenario():
        return await _run(_job("attrib-wire", 6, workers=2), backends)

    _trace, _worker_traces, manager, workers = asyncio.run(
        asyncio.wait_for(scenario(), 60)
    )
    master = manager.metrics.snapshot()
    worker_snaps = [w.metrics.snapshot() for w in workers]
    master_sent = _tag_bytes(master, "send")
    master_received = _tag_bytes(master, "recv")
    workers_sent: dict[str, float] = {}
    workers_received: dict[str, float] = {}
    for snap in worker_snaps:
        for tag, value in _tag_bytes(snap, "send").items():
            workers_sent[tag] = workers_sent.get(tag, 0.0) + value
        for tag, value in _tag_bytes(snap, "recv").items():
            workers_received[tag] = workers_received.get(tag, 0.0) + value

    # Tags whose delivery the job's completion logically guarantees
    # (heartbeats are excluded: a pong can legitimately be in flight at
    # teardown). Master->workers:
    for tag in (
        "handshake_request",
        "handshake_acknowledgement",
        "event_job-started",
        "request_frame-queue_add",
        "request_job-finished",
    ):
        assert master_sent.get(tag, 0.0) > 0.0, tag
        assert master_sent[tag] == workers_received.get(tag), tag
    # Workers->master:
    for tag in (
        "handshake_response",
        "response_frame-queue-add",
        "event_frame-queue_item-finished",
        "response_job-finished",
    ):
        assert workers_sent.get(tag, 0.0) > 0.0, tag
        assert workers_sent[tag] == master_received.get(tag), tag

    # Serialize-time histograms were observed on both ends for the
    # dispatch RPC, one observation per message.
    master_serialize = master[SERIALIZE_METRIC]["series"]
    send_count = master_serialize["tag=request_frame-queue_add,direction=send"][
        "count"
    ]
    recv_count = sum(
        snap[SERIALIZE_METRIC]["series"][
            "tag=request_frame-queue_add,direction=recv"
        ]["count"]
        for snap in worker_snaps
    )
    assert send_count == recv_count == 6


# ---------------------------------------------------------------------------
# Tick profiler


def test_tick_profiler_phase_sum_bounded_by_total():
    registry = MetricsRegistry()
    tracer = Tracer("sched-test", pid=1)
    profiler = TickProfiler(registry, tracer, tick_budget_seconds=0.05)
    for _ in range(3):
        profiler.begin_tick()
        for phase in LOOP_PHASES:
            with profiler.phase(phase):
                time.sleep(0.001)
        profiler.end_tick()
    assert profiler.ticks == 3
    series = registry.snapshot()[TICK_METRIC]["series"]
    total = series["phase=total"]
    assert total["count"] == 3
    phase_sum = sum(
        series[f"phase={phase}"]["sum"] for phase in LOOP_PHASES
    )
    # The phases run inside the tick bracket: their sum cannot exceed
    # the total tick wall time.
    assert 0.0 < phase_sum <= total["sum"]
    budget = registry.snapshot()["sched_tick_budget_ratio"]["series"][""]
    assert math.isfinite(budget) and budget > 0.0
    # Spans landed on the dedicated "sched" track and satisfy the
    # validator's attribution-track invariant (X/i only).
    document = {"traceEvents": tracer.metadata_events() + tracer.events()}
    assert validate_trace_document(document) == []
    tids_by_name = {
        (e.get("args") or {}).get("name"): e.get("tid")
        for e in tracer.metadata_events()
        if e.get("name") == "thread_name"
    }
    sched_tid = tids_by_name["sched"]
    sched_spans = [e for e in tracer.events() if e.get("tid") == sched_tid]
    assert len(sched_spans) == 3 * (len(LOOP_PHASES) + 1)
    assert all(e["ph"] == "X" for e in sched_spans)


def test_tick_profiler_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("TRC_SCHED_PROFILE", "0")
    registry = MetricsRegistry()
    profiler = TickProfiler(registry, None, tick_budget_seconds=0.05)
    profiler.begin_tick()
    with profiler.phase("pricing"):
        pass
    profiler.end_tick()
    observe_dispatch_phase(registry, "dispatch_serialize", 0.01)
    assert registry.snapshot()[TICK_METRIC]["series"] == {}
    observe_dispatch_phase(None, "dispatch_serialize", 0.01)  # no-op, no raise


def test_validator_rejects_stray_phase_on_attribution_track():
    events = [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 7,
         "args": {"name": "sched"}},
        {"ph": "B", "name": "oops", "pid": 1, "tid": 7, "ts": 1.0},
        {"ph": "E", "name": "oops", "pid": 1, "tid": 7, "ts": 2.0},
    ]
    problems = validate_trace_document({"traceEvents": events})
    assert any("attribution track" in p for p in problems)


# ---------------------------------------------------------------------------
# Event-loop lag monitor


def test_blocked_loop_detected_and_flight_recorded(monkeypatch):
    monkeypatch.setenv("TRC_OBS_LOOPMON_INTERVAL", "0.01")
    monkeypatch.setenv("TRC_OBS_LOOPMON_THRESHOLD", "0.05")
    registry = MetricsRegistry()
    tracer = Tracer("loop-test", pid=2)
    flightrec = FlightRecorder(
        span_tracer=tracer, metrics=registry, process_name="loop-test"
    )

    async def scenario():
        monitor = LoopLagMonitor(
            registry, role="master", span_tracer=tracer, flightrec=flightrec
        )
        monitor.start()
        await asyncio.sleep(0.05)  # clean samples under the threshold
        time.sleep(0.12)  # deliberately hold the loop
        await asyncio.sleep(0.05)  # let the late sample land
        await monitor.stop()
        return monitor

    monitor = asyncio.run(asyncio.wait_for(scenario(), 30))
    assert monitor.samples > 0
    assert monitor.blocked_episodes >= 1
    assert monitor.max_lag_seconds >= 0.05
    snapshot = registry.snapshot()
    lag = snapshot[LAG_METRIC]["series"]["role=master"]
    assert lag["count"] == monitor.samples
    assert lag["max"] >= 0.05
    assert snapshot[EPISODES_METRIC]["series"]["role=master"] >= 1
    # The flight recorder fired on the loop_lag trigger (no directory
    # configured: counted + recorded, no file written).
    assert flightrec.triggers.get("loop_lag", 0) >= 1
    assert any(d["trigger"] == "loop_lag" for d in flightrec.view()["dumps"])
    # A "loop blocked" span landed on the dedicated "loop" track, and
    # the whole export passes the validator (incl. invariant 6).
    blocked = [e for e in tracer.events() if e.get("name") == "loop blocked"]
    assert blocked and all(e["ph"] == "X" for e in blocked)
    document = {"traceEvents": tracer.metadata_events() + tracer.events()}
    assert validate_trace_document(document) == []


def scripted(values):
    """A reading that gives `values` in turn, then the last of them for
    ever: every sample behind the scripted ones reads a clock that stands
    still and a process that does nothing, and is on time."""
    remaining = list(values)
    return lambda: remaining.pop(0) if len(remaining) > 1 else remaining[0]


def late_sample(monkeypatch, *, clock, process_time, schedstat, samples=2):
    """One monitor over injected readings: a sample on time, then one that
    the scripted clock makes late. Returns the monitor, its registry and
    its tracer once `samples` samples are in."""
    monkeypatch.setenv("TRC_OBS_LOOPMON_INTERVAL", "0.001")
    monkeypatch.setenv("TRC_OBS_LOOPMON_THRESHOLD", "0.1")
    registry, tracer = MetricsRegistry(), Tracer("loop-cause-test", pid=3)

    async def scenario():
        monitor = LoopLagMonitor(registry, role="worker", span_tracer=tracer)
        monitor.clock = scripted(clock)
        monitor.process_time = scripted(process_time)
        monitor.read_schedstat = scripted(schedstat)
        monitor.start()
        at_start = {
            cause: registry.counter(BLOCKED_SECONDS_METRIC, labels=("role", "cause")).value(role="worker", cause=cause)
            for cause in BLOCKED_CAUSES
        }
        while monitor.samples < samples:
            await asyncio.sleep(0.001)
        await monitor.stop()
        return monitor, at_start

    monitor, at_start = asyncio.run(asyncio.wait_for(scenario(), 30))
    assert at_start == dict.fromkeys(BLOCKED_CAUSES, 0.0)  # every cause, for the role, from the start
    return monitor, registry, tracer


# A sample on time (0 -> 0.001), then one half a second late (1.0 -> 1.501): per sample the clock is
# read twice, the process's CPU once and once more for an episode (three readings in all), schedstat likewise
# and once by start() (four).
LATE_CLOCK = (0.0, 0.001, 1.0, 1.501)
SECONDS_LATE = 0.5


@pytest.mark.parametrize("cause,process_time,schedstat", [
    # the loop's thread stood on a run queue for 0.3 s of the 0.5: the host gave it no CPU
    ("not_scheduled", (5.0, 5.0, 5.01), ("9 1000000000 3", "9 1000000000 3", "9 1000000000 3", "9 1300000000 4")),
    # ... for at least half the lag, whatever our own threads did meanwhile: the first cause that holds
    ("not_scheduled", (5.0, 5.0, 5.45), ("9 1000000000 3", "9 1000000000 3", "9 1000000000 3", "9 1250000000 4")),
    # runnable for 0.1 s only, and the process used 0.4 s of CPU in 0.501 s: our own threads crowded the loop
    ("process_busy", (5.0, 5.0, 5.4), ("9 1000000000 3", "9 1000000000 3", "9 1000000000 3", "9 1100000000 4")),
    # the same where schedstat cannot be read: the CPU clock alone decides
    ("process_busy", (5.0, 5.0, 5.4), (None,)),
    # nobody of ours ran and the loop was not runnable: the whole process stood still
    ("process_idle", (5.0, 5.0, 5.02), ("9 1000000000 3", "9 1000000000 3", "9 1000000000 3", "9 1010000000 4")),
    ("process_idle", (5.0, 5.0, 5.02), (None,)),
    # a schedstat that turns to something else under the monitor's hands is as good as none, and raises nothing
    ("process_idle", (5.0, 5.0, 5.02), ("9 1000000000 3", "9 1000000000 3", "9 1000000000 3", "no numbers here")),
])
def test_a_late_sample_is_counted_under_the_one_cause_the_processes_readings_give(monkeypatch, cause, process_time, schedstat):
    monitor, registry, tracer = late_sample(monkeypatch, clock=LATE_CLOCK, process_time=process_time, schedstat=schedstat)
    assert monitor.blocked_episodes == 1
    seconds = registry.counter(BLOCKED_SECONDS_METRIC, labels=("role", "cause"))
    by_cause = {name: seconds.value(role="worker", cause=name) for name in BLOCKED_CAUSES}
    assert by_cause[cause] == pytest.approx(SECONDS_LATE) and sum(by_cause.values()) == pytest.approx(SECONDS_LATE)
    assert registry.snapshot()[EPISODES_METRIC]["series"]["role=worker"] == 1
    (span,) = [e for e in tracer.events() if e.get("name") == "loop blocked"]
    assert span["args"]["cause"] == cause and span["args"]["lag_s"] == pytest.approx(SECONDS_LATE)
    assert span["args"]["process_cpu_s"] == pytest.approx(process_time[-1] - process_time[-2])
    readable = schedstat[0] is not None and schedstat[-1][-1].isdigit()
    if readable:
        delay = (int(schedstat[-1].split()[1]) - int(schedstat[-2].split()[1])) / 1e9
        assert span["args"]["run_delay_s"] == pytest.approx(delay)
    else:
        assert span["args"]["run_delay_s"] is None
    assert validate_trace_document({"traceEvents": tracer.metadata_events() + tracer.events()}) == []


@pytest.mark.parametrize("schedstat", [None, "", "12345", "one two three", "1 -x 3"])
def test_a_schedstat_that_cannot_be_read_never_raises_and_never_says_not_scheduled(monkeypatch, schedstat):
    from tpu_render_cluster.obs import loopmon

    assert run_delay_seconds(schedstat) is None
    assert run_delay_seconds("4034530 997551 12") == pytest.approx(0.000997551)
    monkeypatch.setattr(loopmon, "SCHEDSTAT_PATH", "/proc/self/no-such-file")
    assert loopmon.read_schedstat() is None
    # whatever the other readings: with no run-queue delay to read, the first cause cannot be given
    for process_cpu_s in (0.0, 0.2, 5.0):
        assert blocked_cause(0.5, 0.75, process_cpu_s, None) in ("process_busy", "process_idle")
    monitor, registry, _ = late_sample(
        monkeypatch, clock=LATE_CLOCK, process_time=(5.0, 5.0, 5.0), schedstat=(schedstat,),
    )
    assert monitor.blocked_episodes == 1
    seconds = registry.counter(BLOCKED_SECONDS_METRIC, labels=("role", "cause"))
    assert seconds.value(role="worker", cause="not_scheduled") == 0.0
    assert seconds.value(role="worker", cause="process_idle") == pytest.approx(SECONDS_LATE)
    # probed once at start(): a host that hides schedstat is not asked again
    assert monitor.read_schedstat() is None


def test_the_causes_edges_are_half_the_lag_and_half_a_core():
    assert blocked_cause(0.4, 0.65, 0.0, 0.2) == "not_scheduled"  # at least half the lag
    assert blocked_cause(0.4, 0.65, 0.0, 0.19) == "process_idle"
    assert blocked_cause(0.4, 0.65, 0.325, 0.19) == "process_busy"  # at least half a core over the sample
    assert blocked_cause(0.4, 0.65, 0.32, 0.19) == "process_idle"
    assert blocked_cause(0.4, 0.65, 9.0, 0.3) == "not_scheduled"  # the first that holds


# ---------------------------------------------------------------------------
# Acceptance e2e: mid-job scrapes + the statistics.json attribution fold


def test_midjob_scrapes_and_attribution_acceptance(monkeypatch):
    """ISSUE 16 acceptance: while a 2-worker scheduler-service run is in
    flight, /metrics on the master shows populated
    ``sched_tick_seconds{phase}`` + ``obs_loop_lag_seconds`` +
    ``transport_message_bytes_total{tag}`` series and a worker endpoint
    shows its own loop-lag + wire families; afterwards the attribution
    fold partitions the run's worker-seconds into fractions summing to
    1.0 +- 0.05."""
    monkeypatch.setenv("TRC_OBS_LOOPMON_INTERVAL", "0.02")
    from tpu_render_cluster.harness.local import _run_multi_job
    from tpu_render_cluster.obs.http import TelemetryServer
    from tpu_render_cluster.obs.prometheus import parse_prometheus
    from tpu_render_cluster.sched.manager import JobManager
    from tpu_render_cluster.sched.models import JobSpec
    from tpu_render_cluster.worker.backends.mock import MockBackend

    specs = [
        JobSpec(job=_job("attrib-a", 6, workers=2)),
        JobSpec(job=_job("attrib-b", 6, workers=2)),
    ]
    backends = [MockBackend(render_seconds=0.08) for _ in range(2)]
    scraped: dict = {}

    async def driver(manager, workers) -> None:
        while manager.telemetry.port == 0:
            await asyncio.sleep(0.01)
        wanted = (
            "sched_tick_seconds_count",
            "obs_loop_lag_seconds_count",
            "transport_message_bytes_total",
        )
        deadline = time.monotonic() + 20.0
        while True:
            parsed = parse_prometheus(
                await asyncio.to_thread(
                    _fetch, manager.telemetry.port, "/metrics"
                )
            )
            # a job's first dispatch waits for the workers' ready events (a
            # tick after its admission): scrape once one has gone out
            dispatched = any(
                labels.get("tag") == "request_frame-queue_add"
                for labels, _value in parsed.get("transport_message_bytes_total", ())
            )
            if dispatched and all(name in parsed for name in wanted):
                scraped["master"] = parsed
                break
            assert time.monotonic() < deadline, (
                f"master families missing mid-job: "
                f"{[n for n in wanted if n not in parsed]}"
            )
            await asyncio.sleep(0.02)
        server = TelemetryServer(workers[0].metrics, port=0)
        await server.start()
        try:
            deadline = time.monotonic() + 20.0
            worker_wanted = (
                "obs_loop_lag_seconds_count",
                "transport_message_bytes_total",
            )
            while True:
                parsed = parse_prometheus(
                    await asyncio.to_thread(_fetch, server.port, "/metrics")
                )
                if all(name in parsed for name in worker_wanted):
                    scraped["worker"] = parsed
                    break
                assert time.monotonic() < deadline, (
                    f"worker families missing mid-job: "
                    f"{[n for n in worker_wanted if n not in parsed]}"
                )
                await asyncio.sleep(0.02)
        finally:
            await server.stop()

    async def scenario():
        started = time.perf_counter()
        worker_traces, job_ids, manager, workers = await _run_multi_job(
            specs,
            backends,
            manager_factory=lambda: JobManager(
                "127.0.0.1", 0, metrics=MetricsRegistry(), telemetry_port=0
            ),
            driver=driver,
        )
        return time.perf_counter() - started, manager, workers

    elapsed, manager, workers = asyncio.run(asyncio.wait_for(scenario(), 120))

    # Mid-job master scrape: every tick phase of the scheduler loop has
    # samples, loop lag was measured, and the wire families carry the
    # dispatch tag.
    master = scraped["master"]
    phases_seen = {
        labels.get("phase")
        for labels, value in master["sched_tick_seconds_count"]
        if value > 0
    }
    # a pass is the timer's (``total``) or one a worker's event started
    # early (``event_total``, master/wakeup.py): with a queue of 2 over
    # mock frames nearly every pass is the second kind
    assert {"total", "event_total"} & phases_seen and "dispatch" in phases_seen
    assert {"fair_share", "share_scan"} <= phases_seen
    assert any(
        value > 0 for _labels, value in master["obs_loop_lag_seconds_count"]
    )
    master_tags = {
        labels.get("tag")
        for labels, value in master["transport_message_bytes_total"]
        if value > 0
    }
    assert "request_frame-queue_add" in master_tags
    # Mid-job worker scrape: its own loop-lag and wire series.
    worker = scraped["worker"]
    assert any(
        labels.get("role") == "worker" and value > 0
        for labels, value in worker["obs_loop_lag_seconds_count"]
    )
    assert any(
        value > 0 for _labels, value in worker["transport_message_bytes_total"]
    )

    # The statistics.json-shaped fold: fractions partition the pool.
    snapshots = [{"written_at": 0.0, "metrics": manager.metrics.snapshot()}]
    snapshots += [
        {"written_at": 0.0, "metrics": w.metrics.snapshot()} for w in workers
    ]
    attribution = summarize_attribution(
        snapshots, worker_seconds=elapsed * len(workers)
    )
    assert attribution is not None
    assert abs(attribution["fractions_sum"] - 1.0) <= 0.05
    assert set(attribution["fractions"]) == set(FRACTION_KEYS)
    assert all(v >= 0.0 for v in attribution["fractions"].values())
    assert attribution["tick"]["ticks"] > 0
    assert attribution["tick"]["phases"]["dispatch"]["count"] > 0
    roles = set(attribution["loop_lag"])
    assert {"master", "worker"} <= roles
    talkers = attribution["top_talkers"]
    assert talkers and any(
        row["tag"] == "request_frame-queue_add" for row in talkers
    )
    assert attribution["fractions"]["transport"] > 0.0
    assert attribution["fractions"]["control_plane"] > 0.0


def test_statistics_attribution_from_run_artifacts(monkeypatch, tmp_path):
    """The artifact path: a persisted 2-worker run's exported traces +
    metrics snapshots fold into summarize_obs with an ``attribution``
    section denominated by the critical-path busy/idle pool."""
    monkeypatch.setenv("TRC_OBS_LOOPMON_INTERVAL", "0.02")
    from tpu_render_cluster.analysis.obs_events import (
        load_cluster_traces,
        load_obs_artifacts,
        summarize_obs,
    )
    from tpu_render_cluster.harness import run_and_persist
    from tpu_render_cluster.worker.backends.mock import MockBackend

    backends = [
        MockBackend(render_seconds=0.02),
        MockBackend(render_seconds=0.06),
    ]
    run_and_persist(_job("attrib-stats", 8, workers=2), backends, tmp_path)
    traces, metrics = load_obs_artifacts(tmp_path)
    cluster_traces = load_cluster_traces(tmp_path)
    summary = summarize_obs(traces, metrics, cluster_traces)
    assert "critical_path" in summary
    attribution = summary["attribution"]
    assert abs(attribution["fractions_sum"] - 1.0) <= 0.05
    assert attribution["worker_seconds"] > 0.0
    assert attribution["fractions"]["transport"] > 0.0
    # Single-job manager: control plane priced off the dispatch
    # serialize/RPC observations, loop lag measured on both roles.
    assert {"master", "worker"} <= set(attribution["loop_lag"])
    assert attribution["top_talkers"]
    # The per-run split exists (one run) and sums to 1 as well.
    per_run = attribution["per_run"]
    assert len(per_run) == 1
    # 5 fractions each rounded to 6 decimals: the exact-1.0 carve can
    # drift by up to 5 * 0.5e-6 after rounding.
    assert abs(sum(next(iter(per_run.values()))["fractions"].values()) - 1.0) < 5e-6


# ---------------------------------------------------------------------------
# Dashboard


def _attrib_samples() -> dict:
    return {
        "sched_tick_seconds_count": [
            ({"phase": "total"}, 10.0),
            ({"phase": "dispatch"}, 10.0),
        ],
        "sched_tick_seconds_sum": [
            ({"phase": "total"}, 0.5),
            ({"phase": "dispatch"}, 0.2),
        ],
        "sched_tick_seconds_bucket": [
            ({"phase": "total", "le": "0.1"}, 10.0),
            ({"phase": "total", "le": "+Inf"}, 10.0),
            ({"phase": "dispatch", "le": "0.1"}, 10.0),
            ({"phase": "dispatch", "le": "+Inf"}, 10.0),
        ],
        "sched_tick_budget_ratio": [({}, 0.4)],
        "obs_loop_lag_seconds_count": [({"role": "master"}, 20.0)],
        "obs_loop_lag_seconds_sum": [({"role": "master"}, 0.02)],
        "obs_loop_lag_seconds_bucket": [
            ({"role": "master", "le": "0.01"}, 20.0),
            ({"role": "master", "le": "+Inf"}, 20.0),
        ],
        "obs_loop_blocked_episodes_total": [({"role": "master"}, 2.0)],
        "transport_message_bytes_total": [
            ({"tag": "request_frame-queue_add", "direction": "send"}, 9000.0),
            ({"tag": "response_heartbeat", "direction": "recv"}, 400.0),
        ],
    }


def test_dashboard_renders_where_did_the_time_go_panel():
    frame = render_dashboard(_attrib_samples(), {}, now=0.0)
    assert "sched tick phase" in frame
    assert "dispatch" in frame
    assert "tick budget used: 0.40x" in frame
    assert "loop lag" in frame
    assert "wire top talkers" in frame
    assert "request_frame-queue_add" in frame
    assert "inf" not in frame


def test_dashboard_degenerate_histograms_never_render_inf():
    # Empty samples: the attribution panel simply doesn't render.
    frame = render_dashboard({}, {}, now=0.0)
    assert "sched tick phase" not in frame and "inf" not in frame
    # A histogram whose ONLY bucket is +Inf (no finite bounds at all):
    # quantiles yield no estimate and the row renders "-", never "inf".
    samples = {
        "sched_tick_seconds_count": [({"phase": "total"}, 5.0)],
        "sched_tick_seconds_sum": [({"phase": "total"}, 0.5)],
        "sched_tick_seconds_bucket": [({"phase": "total", "le": "+Inf"}, 5.0)],
        "master_unit_latency_seconds_bucket": [({"le": "+Inf"}, 3.0)],
    }
    frame = render_dashboard(samples, {}, now=0.0)
    assert "inf" not in frame
    assert "sched tick phase" in frame  # the panel still renders the mean
