"""Obs subsystem tests: registry semantics, tracer export, wire merging,
heartbeat payload serde, and the end-to-end local-harness artifact check
(ISSUE 1 acceptance: mock-backend run emits a loadable Perfetto trace with
master/worker/transport spans plus nonzero frame-phase histograms, and
``analysis/`` loads both files without errors).
"""

import itertools
import json
import math
import os
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from tpu_render_cluster.analysis.obs_events import (
    find_cluster_trace_files,
    find_trace_event_files,
    load_cluster_traces,
    load_metrics_snapshot,
    load_obs_artifacts,
    load_trace_events,
    summarize_obs,
)
from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.obs import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    Tracer,
    export_chrome_trace,
    log_buckets,
    merge_wire,
    validate_trace_document,
    validate_trace_file,
    write_metrics_snapshot,
)
from tpu_render_cluster.protocol import messages as pm

REPO_ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Registry semantics


def test_counter_labels_and_monotonicity():
    registry = MetricsRegistry()
    counter = registry.counter("frames_total", "frames", labels=("worker",))
    counter.inc(worker="w1")
    counter.inc(2.5, worker="w1")
    counter.inc(worker="w2")
    assert counter.value(worker="w1") == 3.5
    assert counter.value(worker="w2") == 1.0
    assert counter.value(worker="nope") == 0.0
    with pytest.raises(ValueError):
        counter.inc(-1.0, worker="w1")
    # Label sets must match the declared dimensions exactly.
    with pytest.raises(ValueError):
        counter.inc(host="w1")
    with pytest.raises(ValueError):
        counter.inc()  # missing the 'worker' label


def test_gauge_set_and_add():
    registry = MetricsRegistry()
    gauge = registry.gauge("queue_depth")
    gauge.set(7)
    assert gauge.value() == 7.0
    gauge.add(-2)
    assert gauge.value() == 5.0


def test_get_or_create_is_idempotent_and_type_checked():
    registry = MetricsRegistry()
    a = registry.counter("x", labels=("k",))
    b = registry.counter("x", labels=("k",))
    assert a is b
    # Same name, different kind or label shape: refused, not silently aliased.
    with pytest.raises(ValueError):
        registry.gauge("x")
    with pytest.raises(ValueError):
        registry.counter("x", labels=("other",))
    # Bucket shape is part of a histogram's identity.
    h = registry.histogram("hist", buckets=(1.0, 2.0))
    assert registry.histogram("hist", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError):
        registry.histogram("hist", buckets=(1.0, 4.0))


def test_log_buckets_shape():
    bounds = log_buckets(1e-4, 1e3, 3)
    assert bounds == DEFAULT_BUCKETS
    assert len(bounds) == 22  # 7 decades * 3/decade + 1, inclusive
    assert bounds[0] == pytest.approx(1e-4)
    assert bounds[-1] == pytest.approx(1e3)
    assert list(bounds) == sorted(bounds)


def test_histogram_bucketing_and_stats():
    registry = MetricsRegistry()
    hist = registry.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for value in (0.005, 0.05, 0.5, 0.5, 5.0):
        hist.observe(value)
    series = hist.series()
    assert series.counts == [1, 1, 2]
    assert series.overflow == 1
    assert series.count == 5
    assert series.sum == pytest.approx(6.055)
    assert series.min == pytest.approx(0.005)
    assert series.max == pytest.approx(5.0)
    # Boundary value lands in its bucket (le semantics: value <= bound).
    hist.observe(0.1)
    assert hist.series().counts == [1, 2, 2]
    with pytest.raises(ValueError):
        registry.histogram("unsorted", buckets=(1.0, 0.1))


def test_registry_snapshot_shape():
    registry = MetricsRegistry()
    registry.counter("c", "help text", labels=("k",)).inc(k="v")
    registry.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    snap = registry.snapshot()
    assert snap["c"]["type"] == "counter"
    assert snap["c"]["series"]["k=v"] == 1.0
    entry = snap["h"]
    assert entry["bucket_bounds"] == [1.0, 2.0]
    # bucket_counts carries the +inf overflow bucket as its last element.
    assert entry["series"][""]["bucket_counts"] == [0, 1, 0]
    json.dumps(snap)  # must be JSON-able as-is


def test_registry_thread_safety():
    registry = MetricsRegistry()
    counter = registry.counter("n", labels=("t",))
    hist = registry.histogram("h")
    n_threads, n_iter = 8, 1000

    def work(tag: str) -> None:
        for i in range(n_iter):
            counter.inc(t=tag)
            counter.inc(t="shared")
            hist.observe(1e-4 * (i + 1))

    threads = [
        threading.Thread(target=work, args=(f"t{i}",)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counter.value(t="shared") == n_threads * n_iter
    for i in range(n_threads):
        assert counter.value(t=f"t{i}") == n_iter
    series = hist.series()
    assert series.count == n_threads * n_iter
    assert sum(series.counts) + series.overflow == series.count


# ---------------------------------------------------------------------------
# Wire form + merging


def test_to_wire_and_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    for registry, count in ((a, 2), (b, 3)):
        registry.counter("frames", labels=("w",)).inc(count, w="x")
        registry.gauge("depth").set(count)
        hist = registry.histogram("lat")
        for _ in range(count):
            hist.observe(0.05)
    merged = merge_wire([a.to_wire(), b.to_wire()])
    assert merged["c"]["frames|w=x"] == 5.0
    assert merged["g"]["depth"] == 5.0
    hist_entry = merged["h"]["lat"]
    assert hist_entry["n"] == 5
    assert hist_entry["s"] == pytest.approx(0.25)
    assert hist_entry["min"] == pytest.approx(0.05)
    assert hist_entry["max"] == pytest.approx(0.05)
    assert sum(hist_entry["b"]) == 5
    assert hist_entry["le"] == list(DEFAULT_BUCKETS)


def test_merge_wire_rejects_mismatched_bounds():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
    b.histogram("lat", buckets=(1.0, 4.0)).observe(1.5)
    with pytest.raises(ValueError, match="bounds mismatch"):
        merge_wire([a.to_wire(), b.to_wire()])


# ---------------------------------------------------------------------------
# Tracer: span nesting + export round-trip


def test_span_nesting_and_export_round_trip(tmp_path):
    tracer = Tracer("test-proc", pid=42)
    with tracer.span("outer", cat="master", track="job"):
        with tracer.span("inner", cat="master", track="job", args={"k": 1}):
            pass
    tracer.instant("marker", track="job")
    path = tracer.export(tmp_path / "trace.json")

    loaded = load_trace_events(path)
    spans = {e["name"]: e for e in loaded.spans()}
    assert set(spans) == {"outer", "inner"}
    outer, inner = spans["outer"], spans["inner"]
    # Same named track -> same tid; viewer nests by ts/dur containment.
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert inner["args"] == {"k": 1}
    # Metadata rows name the process and the track for the viewer.
    meta = {e["name"]: e for e in loaded.events if e["ph"] == "M"}
    assert meta["process_name"]["args"]["name"] == "test-proc"
    assert meta["thread_name"]["args"]["name"] == "job"
    assert any(e["ph"] == "i" for e in loaded.events)


def test_tracer_event_cap_drops_not_grows():
    tracer = Tracer("tiny", max_events=2)
    for i in range(5):
        tracer.complete(f"s{i}", start_wall=0.0, duration=0.001, track="t")
    assert len(tracer.events()) == 2
    assert tracer.dropped == 3


def test_export_chrome_trace_merges_tracers(tmp_path):
    master = Tracer("master")
    worker = Tracer("worker-1")
    with master.span("run job", cat="master", track="job"):
        pass
    with worker.span("render", cat="worker", track="frames"):
        pass
    path = export_chrome_trace(tmp_path / "merged.json", [master, worker])
    loaded = load_trace_events(path)
    pids = {e["pid"] for e in loaded.spans()}
    assert len(pids) == 2  # one Perfetto process row per tracer
    names = {e["args"]["name"] for e in loaded.events if e["name"] == "process_name"}
    assert names == {"master", "worker-1"}


# ---------------------------------------------------------------------------
# Heartbeat metrics payload serde


def test_heartbeat_pong_round_trips_metrics_payload():
    registry = MetricsRegistry()
    registry.counter("worker_frames_rendered_total").inc(4)
    registry.histogram("worker_frame_phase_seconds", labels=("phase",)).observe(
        0.02, phase="render"
    )
    pong = pm.WorkerHeartbeatResponse(metrics=registry.to_wire())
    decoded = pm.decode_message(pm.encode_message(pong))
    assert isinstance(decoded, pm.WorkerHeartbeatResponse)
    assert decoded.metrics == pong.metrics
    merged = merge_wire([decoded.metrics])
    assert merged["c"]["worker_frames_rendered_total"] == 4.0


def test_heartbeat_pong_without_metrics_is_reference_compatible():
    pong = pm.WorkerHeartbeatResponse()
    encoded = pm.encode_message(pong)
    # Wire bytes identical to the reference's empty payload.
    assert json.loads(encoded)["payload"] == {}
    decoded = pm.decode_message(encoded)
    assert decoded.metrics is None


def test_heartbeat_pong_rejects_non_object_metrics():
    with pytest.raises(ValueError):
        pm.WorkerHeartbeatResponse.from_payload({"metrics": [1, 2, 3]})


# ---------------------------------------------------------------------------
# Trace context serde (piggyback compatibility)


def test_queue_add_trace_context_round_trips():
    job = _make_job(2, 1)
    trace = pm.TraceContext.new(pm.generate_trace_id())
    request = pm.MasterFrameQueueAddRequest.new(job, 1, trace=trace)
    decoded = pm.decode_message(pm.encode_message(request))
    assert decoded.trace == trace
    assert decoded.trace.flow_id == f"{trace.span_id:016x}"


def test_queue_add_without_trace_is_reference_compatible():
    job = _make_job(2, 1)
    request = pm.MasterFrameQueueAddRequest.new(job, 1)
    payload = json.loads(pm.encode_message(request))["payload"]
    assert "trace" not in payload  # byte-identical to the reference shape
    assert pm.decode_message(pm.encode_message(request)).trace is None


def test_frame_events_echo_trace_context():
    trace = pm.TraceContext.new(pm.generate_trace_id())
    finished = pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 3, trace=trace)
    assert pm.decode_message(pm.encode_message(finished)).trace == trace
    errored = pm.WorkerFrameQueueItemFinishedEvent.new_errored(
        "j", 3, "boom", trace=trace
    )
    decoded = pm.decode_message(pm.encode_message(errored))
    assert decoded.trace == trace and decoded.error_reason == "boom"
    rendering = pm.WorkerFrameQueueItemRenderingEvent("j", 3, trace=trace)
    assert pm.decode_message(pm.encode_message(rendering)).trace == trace
    # Reference-shaped (no trace) still decodes.
    bare = pm.WorkerFrameQueueItemFinishedEvent.new_ok("j", 3)
    assert pm.decode_message(pm.encode_message(bare)).trace is None


def test_job_started_trace_id_piggyback():
    event = pm.MasterJobStartedEvent(trace_id=42)
    assert pm.decode_message(pm.encode_message(event)).trace_id == 42
    empty = pm.MasterJobStartedEvent()
    assert json.loads(pm.encode_message(empty))["payload"] == {}
    assert pm.decode_message(pm.encode_message(empty)).trace_id is None


def test_heartbeat_pong_round_trips_clock_timestamps():
    pong = pm.WorkerHeartbeatResponse(received_at=123.25, responded_at=123.5)
    decoded = pm.decode_message(pm.encode_message(pong))
    assert decoded.received_at == 123.25
    assert decoded.responded_at == 123.5
    # The empty pong stays byte-identical to the reference's.
    assert json.loads(pm.encode_message(pm.WorkerHeartbeatResponse()))["payload"] == {}


def test_worker_eviction_closes_open_frame_flows(tmp_path):
    """A dead worker's in-flight assignments must not leave dangling flow
    starts: eviction emits a terminal `frame evicted` span per mirrored
    frame, so artifacts from runs that lost a worker still validate."""
    import asyncio

    from tpu_render_cluster.master.queue_mirror import FrameOnWorker
    from tpu_render_cluster.master.state import ClusterManagerState
    from tpu_render_cluster.master.worker_handle import WorkerHandle

    class StubConnection:
        last_known_address = "in-test"

    state = ClusterManagerState(_make_job(2, 1))
    tracer = Tracer("master")
    handle = WorkerHandle(
        0xABCD1234, StubConnection(), state, metrics=None, span_tracer=tracer
    )
    trace = pm.TraceContext.new(state.trace_id)
    # Simulate an in-flight assignment the way queue_frame records it.
    tracer.complete(
        "assign frame", cat="master", start_wall=10.0, duration=0.01,
        track="worker-abcd1234", args={"frame": 1, "flow": trace.flow_id},
    )
    tracer.flow_start(
        "frame", id=trace.flow_id, ts=10.005, cat="frame",
        track="worker-abcd1234", args={"frame": 1},
    )
    handle.queue.add(FrameOnWorker(1, queued_at=10.0, trace=trace))

    asyncio.run(handle._mark_dead("heartbeat failed: test"))

    events = tracer.events()
    evicted = [e for e in events if e.get("name") == "frame evicted"]
    assert len(evicted) == 1
    assert evicted[0]["args"]["frame"] == 1
    terminals = [e for e in events if e.get("ph") == "f"]
    assert [t["id"] for t in terminals] == [trace.flow_id]
    # The exported artifact holds every invariant (no half-open flows).
    assert validate_trace_file(tracer.export(tmp_path / "evict.json")) == []


def test_cluster_trace_finder_requires_separator(tmp_path):
    """Only '<prefix>_cluster_trace-events.json' is a merged timeline; a
    run PREFIX that merely ends in 'cluster' stays a per-process file."""
    (tmp_path / "job-render-cluster_trace-events.json").write_text(
        '{"traceEvents": []}'
    )
    (tmp_path / "run_cluster_trace-events.json").write_text('{"traceEvents": []}')
    assert [p.name for p in find_cluster_trace_files(tmp_path)] == [
        "run_cluster_trace-events.json"
    ]
    assert [p.name for p in find_trace_event_files(tmp_path)] == [
        "job-render-cluster_trace-events.json"
    ]


def test_cluster_timeline_skips_malformed_span_events():
    """A version-skewed worker's junk span_events entries degrade its own
    row instead of crashing the master's end-of-job export."""
    from tpu_render_cluster.master.cluster import ClusterManager
    from tpu_render_cluster.master.worker_handle import WorkerHandle

    class StubConnection:
        last_known_address = "in-test"

    manager = ClusterManager("127.0.0.1", 0, _make_job(2, 1))
    handle = WorkerHandle(
        0x1, StubConnection(), manager.state, metrics=None, span_tracer=None
    )
    good_event = {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 1.0, "dur": 1.0}
    handle.collected_span_events = {
        "process_name": "worker-x",
        "events": [None, "junk", good_event],
    }
    manager.workers[0x1] = handle
    processes = manager.cluster_timeline_processes()
    assert [p.name for p in processes] == ["master", "worker-x"]
    assert processes[1].events == [good_event]


# ---------------------------------------------------------------------------
# merge_wire: mismatched / malformed histogram bucket layouts must raise


def test_merge_wire_rejects_mismatched_bucket_count():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
    b.histogram("lat", buckets=(1.0, 2.0, 4.0)).observe(1.5)
    with pytest.raises(ValueError, match="bounds mismatch.*refusing to misfold"):
        merge_wire([a.to_wire(), b.to_wire()])


def test_merge_wire_rejects_truncated_bucket_vector():
    registry = MetricsRegistry()
    registry.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
    wire = registry.to_wire()
    # Simulate a version-skewed peer that dropped the overflow bucket:
    # zip() would silently misfold these counts without the length check.
    wire["h"]["lat"]["b"] = wire["h"]["lat"]["b"][:-1]
    with pytest.raises(ValueError, match="bucket count vector has 2 entries"):
        merge_wire([wire])
    # Even as the second payload against an already-merged first one.
    good = registry.to_wire()
    with pytest.raises(ValueError, match="bucket count vector"):
        merge_wire([good, wire])


# ---------------------------------------------------------------------------
# Trace-invariant checker (obs/validate.py + scripts/validate_trace.py)


def test_validator_accepts_real_tracer_output(tmp_path):
    tracer = Tracer("proc")
    with tracer.span("outer", cat="x", track="t"):
        with tracer.span("inner", cat="x", track="t"):
            pass
    tracer.complete(
        "spanned", start_wall=100.0, duration=0.5, track="t2", args={"k": 1}
    )
    tracer.flow_start("frame", id="f1", ts=100.25, track="t2")
    tracer.complete("sink", start_wall=101.0, duration=0.5, track="t2")
    tracer.flow_end("frame", id="f1", ts=101.25, track="t2")
    path = tracer.export(tmp_path / "ok_trace-events.json")
    assert validate_trace_file(path) == []


@pytest.mark.parametrize("args,dur_us,problem", [
    ({"frame": 1, "cpu_s": 0.0104}, 10_000.0, None),  # the two clocks tick apart by under a millisecond
    ({"frame": 1}, 10_000.0, None),  # a step handed over without its CPU seconds
    ({"frame": 1, "cpu_s": 0.0121}, 10_000.0, "cpu_s"),  # more CPU than wall: another thread's, or another clock's
    ({"frame": 1, "cpu_s": -0.001}, 10_000.0, "cpu_s"),
    ({"frame": 1, "cpu_s": "much"}, 10_000.0, "cpu_s"),
    ({"mkdir_ms": 1.0, "create_ms": 2.0, "write_ms": 3.0, "close_ms": 0.5, "rename_ms": 3.5}, 10_000.0, None),
    ({"mkdir_ms": 1.0, "create_ms": 2.0, "write_ms": 3.0, "close_ms": 0.5, "rename_ms": 3.6}, 10_000.0, "file operations"),
    ({"mkdir_ms": 1.0, "create_ms": None, "write_ms": 3.0, "close_ms": 0.5, "rename_ms": 1.0}, 10_000.0, "no number"),
])
def test_validator_holds_a_steps_cpu_seconds_and_a_file_writes_operations_inside_its_wall(args, dur_us, problem):
    """Invariant 8: `cpu_s` at most `dur` + 1 ms on every `worker.step`
    event, the five operations at most `dur` on a `file_write` one."""
    event = {"name": "file_write", "cat": "worker.step", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": dur_us, "args": args}
    problems = validate_trace_document({"traceEvents": [event]})
    if problem is None:
        assert problems == []
    else:
        assert len(problems) == 1 and problem in problems[0]
    # the same keys on a span of another category are nobody's business
    assert validate_trace_document({"traceEvents": [{**event, "cat": "worker"}]}) == []


def test_validator_allows_a_coarse_cpu_clock_one_tick_and_no_more():
    """The chip's host counts a thread's CPU in hundredths of a second: a
    2 ms step that a tick's edge falls in reads 0.01 s. Where a process's
    readings are all multiples of their smallest, that is the tolerance;
    a clock as fine as Linux's keeps the millisecond."""
    def step(dur_us, cpu_s, pid=1):  # (each on a track of its own: their order in time is another invariant's)
        return {"name": "encode", "cat": "worker.step", "ph": "X", "pid": pid, "tid": next(tids), "ts": 5.0, "dur": dur_us,
                "args": {"frame": 1, "cpu_s": cpu_s}}

    tids = itertools.count(1)

    ticking = [step(2000.0 + i, (0.0, 0.01, 0.01, 0.0, 0.0)[i % 5]) for i in range(60)] + [step(12600.0, 0.02) for _ in range(3)]
    assert validate_trace_document({"traceEvents": ticking}) == []
    two_ticks_in_five_ms = validate_trace_document({"traceEvents": ticking + [step(5000.0, 0.02)]})
    assert len(two_ticks_in_five_ms) == 1 and "tick of 10000us" in two_ticks_in_five_ms[0]
    # another process of the same file with a fine clock is held to the millisecond
    fine = [step(6000.0, round(0.0011 + 0.000137 * i, 6), pid=2) for i in range(30)]
    assert validate_trace_document({"traceEvents": ticking + fine}) == []
    over = validate_trace_document({"traceEvents": ticking + fine + [step(6000.0, 0.0075, pid=2)]})
    assert len(over) == 1 and "tick of 1000us" in over[0]
    # too few readings to tell a tick from a number: the millisecond
    assert len(validate_trace_document({"traceEvents": [step(2000.0, 0.01) for _ in range(5)]})) == 5


def test_validator_catches_negative_and_missing_timestamps():
    base = {"name": "s", "cat": "", "ph": "X", "pid": 1, "tid": 1}
    assert validate_trace_document(
        {"traceEvents": [{**base, "ts": 0.0, "dur": -5.0}]}
    )
    assert validate_trace_document({"traceEvents": [{**base, "dur": 1.0}]})
    assert validate_trace_document(
        {"traceEvents": [{**base, "ts": -1.0, "dur": 1.0}]}
    )
    assert validate_trace_document({"traceEvents": ["not-an-event"]})
    assert validate_trace_document(["fine-format, bad-event", 3]) != []
    assert validate_trace_document({"no": "traceEvents"}) != []


def test_validator_catches_unbalanced_duration_events():
    begin = {"name": "b", "ph": "B", "pid": 1, "tid": 1, "ts": 1.0}
    end = {"name": "b", "ph": "E", "pid": 1, "tid": 1, "ts": 2.0}
    assert validate_trace_document({"traceEvents": [begin, end]}) == []
    assert validate_trace_document({"traceEvents": [begin]}) != []
    assert validate_trace_document({"traceEvents": [end]}) != []


def test_validator_catches_conflicting_metadata():
    def meta(kind, pid, tid, name):
        return {"name": kind, "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": name}}

    ok = [meta("process_name", 1, 0, "a"), meta("process_name", 2, 0, "b")]
    assert validate_trace_document({"traceEvents": ok}) == []
    clash = [meta("process_name", 1, 0, "a"), meta("process_name", 1, 0, "b")]
    assert any("conflicting process_name" in p
               for p in validate_trace_document({"traceEvents": clash}))
    tid_clash = [meta("thread_name", 1, 7, "x"), meta("thread_name", 1, 7, "y")]
    assert any("conflicting thread_name" in p
               for p in validate_trace_document({"traceEvents": tid_clash}))


def test_validator_catches_non_monotonic_track():
    long_span = {"name": "a", "ph": "X", "pid": 1, "tid": 1,
                 "ts": 0.0, "dur": 1_000_000.0}
    early_end = {"name": "b", "ph": "X", "pid": 1, "tid": 1,
                 "ts": 0.0, "dur": 1_000.0}
    problems = validate_trace_document({"traceEvents": [long_span, early_end]})
    assert any("non-monotonic" in p for p in problems)
    # Nested spans appended inner-first (the tracer's real order) are fine.
    inner = {"name": "i", "ph": "X", "pid": 1, "tid": 1, "ts": 100.0, "dur": 50.0}
    outer = {"name": "o", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 500.0}
    assert validate_trace_document({"traceEvents": [inner, outer]}) == []


def test_validator_catches_unresolvable_flows():
    span = {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 100.0}
    start = {"name": "f", "ph": "s", "id": "f1", "pid": 1, "tid": 1, "ts": 50.0}
    end = {"name": "f", "ph": "f", "bp": "e", "id": "f1", "pid": 1, "tid": 1,
           "ts": 60.0}
    assert validate_trace_document({"traceEvents": [span, start, end]}) == []
    # Start without terminal.
    assert any("without terminal" in p for p in validate_trace_document(
        {"traceEvents": [span, start]}))
    # Terminal without start.
    assert any("without start" in p for p in validate_trace_document(
        {"traceEvents": [span, end]}))
    # A step-only chain is a valid per-process FRAGMENT: the worker
    # daemon's own export routes flows whose start/terminal live on the
    # master's timeline.
    step = {"name": "f", "ph": "t", "id": "f1", "pid": 1, "tid": 1, "ts": 40.0}
    assert validate_trace_document({"traceEvents": [span, step]}) == []
    # Flow event outside any span on its track cannot bind.
    unbound = {**start, "ts": 5000.0}
    assert any("no enclosing span" in p for p in validate_trace_document(
        {"traceEvents": [span, unbound, end]}))


def test_validate_trace_script_cli(tmp_path):
    tracer = Tracer("proc")
    with tracer.span("s", track="t"):
        pass
    good = tracer.export(tmp_path / "good_trace-events.json")
    bad = tmp_path / "bad_trace-events.json"
    bad.write_text(json.dumps(
        {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                          "ts": -1.0, "dur": 1.0}]}
    ))
    script = REPO_ROOT / "scripts" / "validate_trace.py"
    ok = subprocess.run(
        [sys.executable, str(script), str(good)],
        capture_output=True, text=True,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    fail = subprocess.run(
        [sys.executable, str(script), str(good), str(bad)],
        capture_output=True, text=True,
    )
    assert fail.returncode == 1
    assert "FAIL" in fail.stdout and "negative ts" in fail.stdout


# ---------------------------------------------------------------------------
# Snapshot writer


def test_write_metrics_snapshot(tmp_path):
    registry = MetricsRegistry()
    registry.gauge("depth").set(3)
    path = write_metrics_snapshot(
        tmp_path / "metrics.json", registry, extra={"cluster": {"workers": {}}}
    )
    data = load_metrics_snapshot(path)
    assert data["metrics"]["depth"]["series"][""] == 3.0
    assert data["cluster"] == {"workers": {}}
    assert data["written_at"] > 0
    assert not list(tmp_path.glob("*.tmp"))  # atomic replace left no temp file


def test_snapshot_fsyncs_before_atomic_rename(tmp_path, monkeypatch):
    """Crash-safety contract: the rename only ever publishes durable bytes.

    A kill between write and fsync must leave the PREVIOUS snapshot in
    place; fsync must therefore happen before os.replace, on the temp
    file's descriptor."""
    registry = MetricsRegistry()
    registry.gauge("depth").set(1)
    path = tmp_path / "metrics-live.json"

    calls: list[str] = []
    real_fsync, real_replace = os.fsync, os.replace

    def recording_fsync(fd):
        calls.append("fsync")
        return real_fsync(fd)

    def recording_replace(src, dst):
        calls.append("replace")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "fsync", recording_fsync)
    monkeypatch.setattr(os, "replace", recording_replace)
    write_metrics_snapshot(path, registry)
    assert calls == ["fsync", "replace"]

    # Simulated crash after the write but before publication: the
    # established snapshot must survive untouched and stay parseable.
    registry.gauge("depth").set(2)

    def crashing_replace(src, dst):
        raise OSError("simulated kill mid-snapshot")

    monkeypatch.setattr(os, "replace", crashing_replace)
    with pytest.raises(OSError):
        write_metrics_snapshot(path, registry)
    survived = load_metrics_snapshot(path)
    assert survived["metrics"]["depth"]["series"][""] == 1.0


# ---------------------------------------------------------------------------
# End-to-end: local harness (mock backend) -> loadable artifacts


def _make_job(frames: int, workers: int) -> BlenderJob:
    return BlenderJob(
        job_name="obs-test",
        job_description="obs integration test",
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


def test_local_harness_emits_loadable_obs_artifacts(tmp_path):
    from tpu_render_cluster.harness import run_and_persist
    from tpu_render_cluster.worker.backends.mock import MockBackend

    backends = [MockBackend(render_seconds=0.01) for _ in range(2)]
    run_and_persist(_make_job(6, 2), backends, tmp_path)

    traces, metrics = load_obs_artifacts(tmp_path)
    assert len(traces) == 1 and len(metrics) == 1

    # Every exported timeline passes the trace-invariant checker.
    for trace_file in find_trace_event_files(tmp_path) + find_cluster_trace_files(
        tmp_path
    ):
        assert validate_trace_file(trace_file) == [], trace_file

    # Master, worker, AND transport spans present in one merged timeline.
    cats = traces[0].span_count_by_category()
    assert cats.get("master", 0) > 0
    assert cats.get("worker", 0) > 0
    assert cats.get("transport", 0) > 0
    # Every frame contributes its four phase spans on some worker row.
    by_name = traces[0].span_seconds_by_name()
    for phase in ("queue_wait", "read", "render", "write"):
        assert len(by_name[phase]) == 6, phase
    assert all(d >= 0.01 for d in by_name["render"])

    # Metrics snapshot: nonzero frame-phase histograms, both in each
    # worker's full snapshot and in the wire-merged cluster aggregate.
    snapshot = metrics[0]
    merged = snapshot["workers_wire_merged"]
    for phase in ("queue_wait", "read", "render", "write"):
        entry = merged["h"][f"worker_frame_phase_seconds|phase={phase}"]
        assert entry["n"] == 6, phase
        assert entry["s"] > 0 or phase == "queue_wait"
    assert merged["c"]["worker_frames_rendered_total"] == 6.0
    per_worker = snapshot["workers"]
    assert len(per_worker) == 2
    total = sum(
        series["count"]
        for worker_snap in per_worker.values()
        for series in worker_snap["worker_frame_phase_seconds"]["series"].values()
    )
    assert total == 6 * 4
    # Master-side series: assignment latency observed per strategy.
    master_metrics = snapshot["metrics"]
    lat = master_metrics["master_assignment_latency_seconds"]["series"]
    assert sum(s["count"] for s in lat.values()) == 6
    assert snapshot["cluster"]["frames_finished"] == 6

    # The analysis roll-up consumes both without errors.
    summary = summarize_obs(traces, metrics)
    assert summary["spans_by_category"]["worker"] >= 24
    assert summary["span_duration_stats"]["render"]["count"] == 6
    assert math.isfinite(summary["span_duration_stats"]["render"]["p95_s"])


# ---------------------------------------------------------------------------
# End-to-end: merged cluster timeline + critical-path analysis
# (ISSUE 3 acceptance: a two-worker harness run emits one valid
# cluster_trace-events.json with per-worker process tracks and a
# master->worker flow link per frame, and statistics.json gains a
# critical_path section with per-worker straggler scores.)


def test_cluster_timeline_and_critical_path_end_to_end(tmp_path):
    from tpu_render_cluster.analysis import run_all
    from tpu_render_cluster.harness import run_and_persist
    from tpu_render_cluster.worker.backends.mock import MockBackend

    frames = 8
    # A deliberate straggler: worker 2 renders 5x slower than worker 1.
    backends = [
        MockBackend(render_seconds=0.01),
        MockBackend(render_seconds=0.05),
    ]
    run_and_persist(_make_job(frames, 2), backends, tmp_path)

    # Exactly one merged cluster timeline, and it passes the invariant
    # checker (balanced events, monotonic tracks, unique pid metadata,
    # resolvable flows).
    cluster_files = find_cluster_trace_files(tmp_path)
    assert len(cluster_files) == 1
    assert cluster_files[0].name.endswith("_cluster_trace-events.json")
    assert validate_trace_file(cluster_files[0]) == []
    # ...and the per-process finder does NOT double-count it.
    assert cluster_files[0] not in find_trace_event_files(tmp_path)

    document = json.loads(cluster_files[0].read_text())
    events = document["traceEvents"]

    # One process track per worker (plus the master's), each on its own pid.
    pids_by_name = {
        e["args"]["name"]: e["pid"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    worker_names = [n for n in pids_by_name if n.startswith("worker-")]
    assert "master" in pids_by_name and len(worker_names) == 2
    assert len(set(pids_by_name.values())) == 3
    master_pid = pids_by_name["master"]
    worker_pids = {pids_by_name[n] for n in worker_names}

    # The applied clock offsets are recorded (one per process; in-process
    # colocation keeps them tiny but they went through the real NTP path).
    offsets = document["otherData"]["clock_offsets_seconds"]
    assert set(offsets) == set(pids_by_name)
    assert all(abs(v) < 0.5 for v in offsets.values())

    # At least one master->worker flow link per rendered frame: a flow
    # start on the master pid whose id is routed/terminated on a worker pid.
    flow_sides: dict[str, set[int]] = {}
    flow_frames: dict[str, int] = {}
    for event in events:
        if event.get("ph") in ("s", "t", "f"):
            flow_sides.setdefault(event["id"], set()).add(event["pid"])
            frame = (event.get("args") or {}).get("frame")
            if frame is not None:
                flow_frames[event["id"]] = frame
    linked_frames = {
        flow_frames[flow_id]
        for flow_id, pids in flow_sides.items()
        if master_pid in pids and pids & worker_pids and flow_id in flow_frames
    }
    assert linked_frames == set(range(1, frames + 1))

    # The heartbeat estimator ran for both workers (ping-first heartbeat):
    # offset gauges are in the master registry snapshot.
    _, metrics = load_obs_artifacts(tmp_path)
    offset_series = metrics[0]["metrics"]["master_worker_clock_offset_seconds"][
        "series"
    ]
    assert len(offset_series) == 2
    assert all(abs(v) < 0.5 for v in offset_series.values())

    # Full pipeline: run_all folds the critical_path section (per-worker
    # straggler scores, idle attribution, makespan path) into
    # statistics.json.
    out_dir = tmp_path / "analysis-out"
    assert (
        run_all.main(
            ["--results", str(tmp_path), "--out", str(out_dir), "--no-plots"]
        )
        == 0
    )
    stats = json.loads((out_dir / "statistics.json").read_text())
    sections = stats["obs"]["critical_path"]
    assert len(sections) == 1
    section = next(iter(sections.values()))
    assert section["frames"] == frames
    workers = section["workers"]
    assert len(workers) == 2
    scores = sorted(w["straggler_score"] for w in workers.values())
    assert scores[0] <= 1.0 <= scores[1] and scores[1] > scores[0]
    assert all("idle_s" in w and "phase_p50_s" in w for w in workers.values())
    assert section["stragglers"][0] == max(
        workers, key=lambda w: workers[w]["straggler_score"]
    )
    # The makespan path is dominated by render segments, and the analysis
    # agrees with the merged timeline loader.
    path_section = section["critical_path"]
    assert path_section["seconds_by_kind"].get("render", 0.0) > 0.0
    cluster_traces = load_cluster_traces(tmp_path)
    assert len(cluster_traces) == 1
    summary = summarize_obs([], [], cluster_traces)
    assert next(iter(summary["critical_path"].values()))["frames"] == frames


# ---------------------------------------------------------------------------
# Launch occupancy of deep mesh frames: backend -> registry -> statistics.json


@pytest.fixture
def launch_registry(monkeypatch):
    """A fresh process-global registry, fed two deep frames' bounce launches
    through the backend's own feeder: (live rays, width) per launch."""
    import numpy as np

    from tpu_render_cluster import obs
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    monkeypatch.setattr(obs, "_global_registry", MetricsRegistry())
    for launches in ([[2048, 2048], [1500, 2048], [300, 1024]], [[2048, 2048], [256, 1024]]):
        TpuRaytraceBackend._observe_launches(np.asarray(launches, np.int32))
    return obs.get_registry()


LAUNCHES = 5
LAUNCHED = 2048 + 2048 + 1024 + 2048 + 1024
LIVE = 2048 + 1500 + 300 + 2048 + 256
OCCUPANCY_MEAN = (1 + 1500 / 2048 + 300 / 1024 + 1 + 256 / 1024) / 5


@pytest.mark.parametrize("form", ["registry_snapshot", "heartbeat_wire", "no_deep_frame"])
def test_summarize_launch_occupancy(form, launch_registry):
    from tpu_render_cluster.analysis.obs_events import summarize_launch_occupancy

    if form == "registry_snapshot":
        # the harness's shape: the process registry under its pid, newest copy only
        older = {"written_at": 1.0, "process_metrics": {"pid": 7, "metrics": {}}}
        newest = {
            "written_at": 2.0,
            "process_metrics": {"pid": 7, "metrics": launch_registry.snapshot()},
        }
        snapshots = [newest, older]
    elif form == "heartbeat_wire":
        # the master CLI's shape: two workers' heartbeat payloads, merged
        wire = merge_wire([launch_registry.to_wire(), launch_registry.to_wire()])
        snapshots = [{"metrics": {}, "cluster_metrics": wire}]
    else:
        # a sphere job: frames were rendered, no bounce launch was counted
        other = MetricsRegistry()
        other.counter("render_tier_frames_total", labels=("tier",)).inc(4, tier="masked")
        snapshots = [{"metrics": other.snapshot()}, {"cluster_metrics": other.to_wire()}]
    summary = summarize_launch_occupancy(snapshots)
    if form == "no_deep_frame":
        assert summary is None
        return
    workers = 2 if form == "heartbeat_wire" else 1
    assert summary["launches"] == workers * LAUNCHES
    assert summary["launched_lanes_total"] == workers * LAUNCHED
    assert summary["live_lanes_total"] == workers * LIVE
    assert summary["launch_occupancy_mean"] == pytest.approx(OCCUPANCY_MEAN)
    assert summary["live_lane_share"] == pytest.approx(LIVE / LAUNCHED)


def test_launch_occupancy_series_flow_into_statistics(tmp_path, launch_registry):
    """Backend -> registry -> snapshot file -> one section of the summary."""
    write_metrics_snapshot(tmp_path / "run_metrics.json", launch_registry)
    traces, metrics = load_obs_artifacts(tmp_path)
    summary = summarize_obs(traces, metrics)
    assert "wavefront" not in summary and "raypool" not in summary
    section = summary["launch_occupancy"]
    assert section["launches"] == LAUNCHES
    assert section["live_lane_share"] == pytest.approx(LIVE / LAUNCHED)
    assert 0.0 < section["launch_occupancy_mean"] <= 1.0
