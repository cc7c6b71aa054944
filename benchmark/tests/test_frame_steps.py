"""The per-layer metrics that read a frame's steps and the loop's states
are data files: every one resolves to a number on a rehearsal's scrapes,
and the timeline that now carries `worker.step` events reads, for the trace
reducer, exactly as it read before."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest as manifest_lib
from benchmark.lib import trace_reduce as tr
from benchmark.tests.test_rehearsal import ROOT, rehearse

STEP_METRICS = {
    "resolve_ms_per_frame", "dispatch_ms_per_frame", "device_wait_ms_per_frame", "readback_ms_per_frame",
    "host_syncs_per_frame", "encode_ms_per_frame", "file_write_ms_per_frame",
    "starved_ms_per_frame", "report_ms_per_frame", "loop_blocked_episodes",
}
NEW_METRICS = STEP_METRICS | {"pool_live_lane_share", "wavefront_launch_occupancy"}


def test_the_new_metrics_are_data_files_and_nothing_else():
    directory = ROOT / "benchmark" / "layer_metrics"
    for name in NEW_METRICS:
        spec = json.loads((directory / f"{name}.json").read_text())
        assert spec["reader"] in ("delta", "delta_ratio") and spec["from"] == "workers"
        assert not (directory / f"{name}.py").exists()
    assert manifest_lib.validate(ROOT) == []
    listed = {m["name"]: m for m in manifest_lib.load_benchmark(ROOT)["per_layer"]}
    every_cell = {w["name"] for w in manifest_lib.load_benchmark(ROOT)["workloads"]}
    for name in STEP_METRICS:  # every cell runs the worker's frame loop (the service cells since PR 44)
        assert set(listed[name]["workloads"]) == every_cell
    assert set(listed["pool_live_lane_share"]["workloads"]) == {w for w in every_cell if w.endswith("-1w-queued")}
    assert listed["wavefront_launch_occupancy"]["workloads"] == ["03ph2mesh-1w-fine"]


# A mesh cell's rehearsal is short: at 64x64 the CPU ends the job's backlog
# of 180 frames within a minute, and the trace has to be written before that.
@pytest.mark.parametrize("cell,seconds", [
    ("04vs-1w-coarse", 10), ("03ph2mesh-1w-queued", 4), ("03ph2mesh-1w-fine", 4),
])
def test_every_new_metric_of_a_cell_is_a_number_on_a_rehearsals_line(cell, seconds):
    result = rehearse(ROOT, cell, trace=1, seconds=seconds)
    wanted = {m["name"] for m in manifest_lib.load_cell(cell, ROOT).per_layer} & NEW_METRICS
    assert STEP_METRICS <= wanted
    for name in wanted:
        assert isinstance(result["metrics"][name]["value"], float), name
    metrics = {name: result["metrics"][name]["value"] for name in wanted}
    assert metrics["device_wait_ms_per_frame"] > 0 and metrics["encode_ms_per_frame"] > 0
    assert metrics["host_syncs_per_frame"] == 1.0  # a frame is one program in every cell since PR 30
    if cell == "03ph2mesh-1w-fine":
        assert 0 < metrics["wavefront_launch_occupancy"] <= 100
    if cell == "03ph2mesh-1w-queued":
        assert 0 < metrics["pool_live_lane_share"] <= 100
    # the steps add up to the phases they lie in
    in_render = sum(metrics[f"{s}_ms_per_frame"] for s in ("resolve", "dispatch", "device_wait", "readback"))
    assert in_render <= result["metrics"]["render_ms_per_frame"]["value"] * 1.02 + 5.0
    in_write = metrics["encode_ms_per_frame"] + metrics["file_write_ms_per_frame"]
    assert in_write == pytest.approx(result["metrics"]["save_ms_per_frame"]["value"], rel=0.03, abs=0.2)


def test_a_timeline_with_steps_reads_as_it_read_without_them(tmp_path):
    """`worker_phase_spans` takes cat == "worker" and read/render/write, one
    at a time; the steps are `worker.step` on a track of their own."""
    from tpu_render_cluster.obs import Tracer
    from tpu_render_cluster.traces.worker_trace import FrameRenderTime
    from tpu_render_cluster.worker.queue import QueuedFrame, WorkerAutomaticQueue

    def timeline(with_steps: bool) -> Path:
        tracer = Tracer("worker-x")
        queue = WorkerAutomaticQueue(None, None, None, None, span_tracer=tracer)
        for frame in range(3):
            at = 100.0 + frame
            steps = (
                ("resolve", at, 0.01), ("dispatch", at + 0.01, 0.02), ("device_wait", at + 0.03, 0.3),
                ("dispatch", at + 0.33, 0.02), ("device_wait", at + 0.35, 0.2), ("readback", at + 0.55, 0.05),
                ("file_write", at + 0.6, 0.001), ("encode", at + 0.601, 0.1), ("file_write", at + 0.701, 0.099),
            )
            timing = FrameRenderTime(at, at + 0.01, at + 0.01, at + 0.6, at + 0.6, at + 0.8, at + 0.81,
                                     steps=steps if with_steps else ())
            job = SimpleNamespace(job_name="a-job")  # a frame's spans carry their job's name since PR 43
            queue._observe_frame_phases(QueuedFrame(job, frame, queued_at=at - 0.5), timing)
        return tracer.export(tmp_path / f"worker-{with_steps}_trace-events.json")

    with_steps, without = timeline(True), timeline(False)
    events = json.loads(with_steps.read_text())["traceEvents"]
    assert sum(1 for e in events if e.get("cat") == "worker.step") == 27
    spans = tr.worker_phase_spans(with_steps)
    assert spans == tr.worker_phase_spans(without)
    assert [name for name, _, _ in spans] == ["read", "render", "write"] * 3
    for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
        assert start >= end  # still one at a time
