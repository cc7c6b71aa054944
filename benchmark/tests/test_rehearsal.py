"""A CPU rehearsal of whole runs at a tiny size, and the proof that a cell
is data: a new configuration, traffic mix, cell and per-layer metric are
added to a copy as files and entries, nothing that is there is edited, and
the harness lists and runs them.

These start a master and workers as real processes; about a minute each.
"""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.lib import manifest as manifest_lib

ROOT = Path(__file__).resolve().parents[2]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(root, cell, trace, seconds=10):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "11",
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_a_whole_run_prints_the_contracts_last_line(trace):
    result = rehearse(ROOT, "04vs-1w-coarse", trace)
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 10
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    names = set(result["metrics"])
    if trace:
        assert {"render_ms_per_frame", "save_ms_per_frame", "worker_idle_share", "compiles_in_window"} <= names
        assert not names & {"frames_per_s", "setup_s"}
        # no device plane on the CPU: the trace's readers return nothing
        assert not names & {"device_idle_share", "kernel_ms_per_frame", "host_glue_ms_per_frame"}
    else:
        assert names == {"frames_per_s", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and isinstance(metric["value"], float)


def test_without_a_tpu_there_is_no_result():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "04vs-1w-coarse", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert '"correct"' not in done.stdout and "no accelerator" in done.stderr


def copy_of_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (tmp_path / "tpu_render_cluster").symlink_to(ROOT / "tpu_render_cluster")
    return tmp_path / "benchmark"


def rehearse_a_short_job(tmp_path, frames_left, seconds):
    """A traced rehearsal of `03ph2mesh-1w-queued` in a copy whose job has
    `frames_left` frames (a higher `frame_range_from`, as ISSUE 44 has it:
    on a scratch copy of the configuration, never in the tree)."""
    bench = copy_of_the_benchmark(tmp_path)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = bench / "configs/03ph2mesh-480f-1w/config.json"
    config = json.loads(path.read_text())
    config["frame_range_from"].update(first=config["frames"] - frames_left + 1, span=1)
    path.write_text(json.dumps(config))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "03ph2mesh-1w-queued", "--seed", "2900000011",
         "--seconds", str(seconds), "--trace", "1", "--rehearse"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=600,
    )


def test_a_job_that_ends_while_the_profile_is_written_is_no_fault(tmp_path):
    """The measurement is made at the window's end; the master that leaves
    with its job after it, and the worker after the master, exit 0, and the
    run's line is whole. 44 frames: 8 of warm-up, then at the 1 to 5
    frames/s of a 64x64 frame on a CPU the job ends between the end of a
    6 s window and the 20-30 s the interpreter's profile takes to write."""
    done = rehearse_a_short_job(tmp_path, frames_left=44, seconds=6)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    (stopped,) = [line for line in lines if line.get("stage") == "stopped"]
    (window,) = [line for line in lines if line.get("stage") == "window"]
    assert stopped["master_exit_code"] == 0 and stopped["worker_exit_codes"] == [0], stopped
    assert 0.0 <= stopped["master_left_s_after_window"] < max(stopped["profiles_written_s_after_window"]), stopped
    assert window["backlog"] == 44 and 0 < window["backlog_left"] < 44 - 8
    result = lines[-1]
    assert set(result) - {"breakdown"} == RESULT_KEYS and result["correct"] is True and result["failed"] == 0
    wanted = {m["name"] for m in manifest_lib.load_cell("03ph2mesh-1w-queued", ROOT).per_layer if m["source"] != "device_trace"}
    # no device plane on the CPU; every other accepted metric is on the line, the worker's snapshot was found
    assert wanted - {"host_glue_ms_per_frame"} <= set(result["metrics"])


def test_a_job_that_ends_inside_the_window_says_so(tmp_path):
    done = rehearse_a_short_job(tmp_path, frames_left=18, seconds=10)
    assert done.returncode == 1 and '"correct"' not in done.stdout
    last = done.stderr.strip().splitlines()[-1]
    assert re.fullmatch(
        r"benchmark: FAILED: the job's backlog of 18 frames ended [0-9.]+ s into the window of 10 s at [0-9.]+ frames/s; "
        r"frame_range_from of 03ph2mesh-480f-1w holds to 0\.215\d* frames/s", last,
    ), last


def test_a_cell_is_data(tmp_path):
    bench = copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    # one configuration: the sequential baseline on a shorter backlog
    shutil.copytree(bench / "configs/04vs-14400f-1w", bench / "configs/04vs-6000f-1w")
    config = json.loads((bench / "configs/04vs-6000f-1w/config.json").read_text())
    # 6000 frames: a 64x64 frame takes a CPU 15 ms, and a job that ends inside the window fails the run
    config.update(name="04vs-6000f-1w", frames=6000, frame_range_from={"first": 1, "span": 300})
    del config["holds_frames_per_s"]  # a configuration may state none
    config["check"]["frames"]["after"] = 16  # a 6 s rehearsal window holds fewer frames than the chip's
    (bench / "configs/04vs-6000f-1w/config.json").write_text(json.dumps(config))
    # one traffic mix: naive-fine with a short warm-up
    (bench / "traffic/backlog-fine-short.json").write_text(json.dumps({
        "name": "backlog-fine-short", "driver": "backlog",
        "strategy": {"strategy_type": "naive-fine"}, "warmup_frames_per_worker": 2,
    }))
    # one declarative per-layer metric
    (bench / "layer_metrics/queue_wait_ms_per_frame.json").write_text(json.dumps({
        "reader": "delta_ratio", "from": "workers", "scale": 1000.0,
        "numerator": {"series": "worker_frame_phase_seconds_sum", "labels": {"phase": "queue_wait"}},
        "denominator": {"series": "worker_frame_phase_seconds_count", "labels": {"phase": "queue_wait"}},
    }))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "04vs-6000f-1w", "source": "test", "file": "benchmark/configs/04vs-6000f-1w/config.json",
        "reduced": ["frames"], "why": "test"})
    manifest["workloads"].append({
        "name": "04vs-1w-fine", "config": "04vs-6000f-1w", "traffic": "backlog-fine-short",
        "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "queue_wait_ms_per_frame", "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "worker runtime", "moves": "frames_per_s", "workloads": ["04vs-1w-fine"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    listed = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=tmp_path, capture_output=True, text=True, timeout=60,
    )
    assert listed.returncode == 0, listed.stderr
    assert "04vs-1w-fine" in listed.stdout and "queue_wait_ms_per_frame" in listed.stdout
    result = rehearse(tmp_path, "04vs-1w-fine", trace=1, seconds=6)
    assert result["correct"] is True
    assert result["metrics"]["queue_wait_ms_per_frame"]["value"] >= 0.0
    # the new metric belongs to the new cell only
    old_cell = manifest_lib.load_cell("04vs-1w-coarse", tmp_path)
    assert "queue_wait_ms_per_frame" not in {m["name"] for m in old_cell.per_layer}
    # nothing that was there was edited
    assert all(path.read_bytes() == content for path, content in before.items())
