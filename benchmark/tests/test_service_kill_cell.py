"""The cell `svc2fam-4w-kill1` as data: it finds its files, its six metrics
find their readers and list the new cell alone, the configuration reads
the pool configuration it names, a program without the new series gives the
readers nothing to read, and a whole run of it walks through on the CPU.

The rehearsal starts `master serve` and FOUR workers as real processes,
kills one 8 s into a window of 20 s and goes on until the jobs that were in
hand are finished (the reconnect window is the deployment's 30 s, so about
40 s after the kill), at 64x64 through the Pallas interpreter: about two
minutes on eight cores; it has a time limit of its own. Untraced.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.drivers import service_kill
from benchmark.lib import check, manifest, readers

ROOT = Path(__file__).resolve().parents[2]
CELL, POOL_CELL = "svc2fam-4w-kill1", "svc2fam-4w-closed12"
REHEARSAL_SECONDS = 1200
NEW_METRICS = {
    "kill_to_eviction_s", "stranded_units", "stranded_recover_s", "survivor_starved_s_max",
    "jobs_blocked_on_silent_s", "pool_frames_per_s_after_kill",
}


def test_the_cell_is_data_and_says_what_the_issue_says():
    assert manifest.validate(ROOT) == []
    listing = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert listing.returncode == 0 and listing.stderr == ""
    assert "svc2fam-4w-kill1       config svc-04vs-03ph2scan-4w-lose1 traffic service-closed12-kill1 chips 4" in listing.stdout
    benchmark = manifest.load_benchmark(ROOT)
    assert len(benchmark["workloads"]) == 9 and sum(w["chips"] == 4 for w in benchmark["workloads"]) == 3
    cell = manifest.load_cell(CELL, ROOT)
    pool = manifest.load_cell(POOL_CELL, ROOT)
    # the traffic: the pool's loop, unedited, and the kill
    assert cell.traffic["driver"] == "service_kill"
    for key in ("loop", "jobs_in_hand", "poll_seconds", "warmup_jobs", "warmup_every_family", "warmup_every_worker",
                "strategy", "weight", "priority"):
        assert cell.traffic[key] == pool.traffic[key], key
    kill = cell.traffic["kill"]
    assert (kill["worker"], kill["signal"], kill["at_s"], kill["replaced"], kill["settle_s"]) == (
        "mix(seed) mod 4", "SIGKILL", 8.0, False, 40,
    )
    # the metrics: the new six under the new cell alone, the pool cell's lists with the new cell's name added
    names = {metric["name"] for metric in cell.per_layer}
    assert NEW_METRICS < names
    assert all(m["workloads"] == [CELL] for m in benchmark["per_layer"] if m["name"] in NEW_METRICS)
    assert names - NEW_METRICS == {metric["name"] for metric in pool.per_layer}
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}
    # the configuration: the pool configuration by name, what it keeps, assumes and guarantees beside
    merged = service_kill.with_base(cell).config
    assert cell.config["base"] == pool.config_name and cell.config["architecture"] is None
    for key in ("sequence", "job_name_format", "output_directory_format", "check", "deployment"):
        assert key not in cell.config and merged[key] == pool.config[key], key
    assert cell.config["families"] == merged["families"] == pool.config["families"]  # the harness's own copy
    assert merged["workers"] == 4 and merged["job_barrier"] == 1 and merged["trace_slice_s"] == 15
    assert set(pool.config["guarantees"]) < set(merged["guarantees"])
    assert set(merged["guarantees"]) - set(pool.config["guarantees"]) == {
        "no_unit_lost_to_a_dead_worker", "a_rerender_states_its_cause", "a_dead_workers_leavings_are_not_output",
        "a_reconnect_inside_the_window_keeps_its_queue",
    }
    assert set(merged["kept"]) == {"heartbeat", "reconnect_window", "scheduler"}
    entry = next(c for c in benchmark["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == ["workers", "frame_range_from", "samples", "jobs"] == list(cell.config["reduced"])
    assert len({c["source"] for c in benchmark["configs"]}) == len({c["file"] for c in benchmark["configs"]}) == 8


def test_the_victim_is_the_seeds_and_every_worker_is_somebodys():
    victims = {check.mix(seed) % 4 for seed in range(4900000000, 4900000040)}
    assert victims == {0, 1, 2, 3}


def test_the_new_readers_find_nothing_in_a_program_without_the_series():
    """The parent's side of a line: no `kill`, no counter, no value, no exception."""
    empty = {
        "window_s": 45.0, "workers": 4, "frames_per_s": 60.0, "files": [], "cache_entries_delta": 0,
        "scrapes": {"master": ([{}], [{}]), "workers": ([{}] * 4, [{}] * 4)}, "trace": None, "jobs": [],
    }
    assert {readers.read_metric(name, empty, ROOT) for name in NEW_METRICS} == {None}
    silent = {**empty, "kill": {
        "worker": "0a", "index": 1, "at": 108.0, "evicted_at": None, "window_start": 100.0, "window_end": 145.0,
        "in_hand": [], "stranded": None, "survivor_scrapes": ([{}] * 3, [{}] * 3), "files_after": None,
    }}
    assert {readers.read_metric(name, silent, ROOT) for name in NEW_METRICS} == {None}


def test_the_new_readers_read_what_the_run_and_the_program_say():
    key = lambda name, **labels: (name, tuple(sorted(labels.items())))  # noqa: E731
    blocked = "sched_job_blocked_on_silent_worker_seconds_total"
    no_work = lambda seconds: {key("worker_loop_seconds_total", state="no_work"): seconds}  # noqa: E731
    run = {
        "window_s": 45.0, "workers": 4,
        "scrapes": {"master": ([{key(blocked): 0.0}], [{key(blocked): 61.5}]), "workers": ([{}] * 4, [{}] * 4)},
        "kill": {
            "worker": "0a", "index": 1, "at": 108.0, "evicted_at": 138.25, "window_start": 100.0, "window_end": 145.0,
            "in_hand": ["a", "b"],
            "stranded": [("a", 7, 138.25, 138.9), ("b", 40, 138.25, 139.5)],
            "survivor_scrapes": ([no_work(1.0), no_work(2.0), no_work(0.5)], [no_work(1.4), no_work(2.1), no_work(1.75)]),
            "files_after": [108.5, 109.0, 111.0, 120.0, 144.0, 146.0],
        },
    }
    values = {name: readers.read_metric(name, run, ROOT) for name in NEW_METRICS}
    assert values == {
        "kill_to_eviction_s": 30.25, "stranded_units": 2.0, "stranded_recover_s": 31.5,
        "survivor_starved_s_max": 1.25, "jobs_blocked_on_silent_s": 61.5,
        "pool_frames_per_s_after_kill": 3 / 35.0,
    }
    nothing_stranded = {**run, "kill": {**run["kill"], "stranded": []}}
    assert readers.read_metric("stranded_recover_s", nothing_stranded, ROOT) == 0.0
    assert readers.read_metric("stranded_units", nothing_stranded, ROOT) == 0.0


def test_a_whole_run_of_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "4900000111",
         "--seconds", "20", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    window = next(line for line in lines if line["stage"] == "window")
    assert 8.0 <= window["killed_after_s"] < 8.5 and window["killed_worker"] == check.mix(4900000111) % 4
    assert 30.0 <= window["evicted_after_s"] < 33.0 and window["settled_after_s"] <= 40.5
    stopped = next(line for line in lines if line["stage"] == "stopped")
    assert [state for _at, state in stopped["said_of_victim"]][-2:] == ["silent", "dead"]
    failover = next(line for line in lines if line["stage"] == "failover")
    assert failover["problems"] == 0 and len(failover["in_hand"]) == 12 and failover["late"] == []
    assert failover["rendered_twice"]["unexplained"] == [] and failover["leavings"] == []
    assert all(unit["rendered_again_at"] and unit["file_at"] for unit in failover["stranded"])
    pool = next(line for line in lines if line["stage"] == "pool")["workers"]
    assert len(pool) == 4 and sum(worker["killed"] for worker in pool) == 1
    checked = next(line for line in lines if line["stage"] == "check")
    for family in window["families"]:  # a frame a family of every survivor AND of the killed worker
        by_worker = checked[family]["by_worker"]
        assert len(by_worker) == 4 and sum("(killed)" in name for name in by_worker) == 1
        assert all(one.get("agreement", 1.0) >= 0.97 for one in by_worker.values())
    read = next(line for line in lines if line["stage"] == "kill_metrics")
    assert set(read) == {"stage", *NEW_METRICS} and all(value is not None for value in read.values())
