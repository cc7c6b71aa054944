"""The cell `svc2fam-1w-closed3` as data: it finds its files, its metrics
find their readers, a program without the service's series gives them
nothing to read, and a whole run of it walks through on the CPU.

The rehearsal starts `master serve` and a worker as real processes and
serves both families at 64x64 through the Pallas interpreter (the scan
family's 871,200-triangle scene included), about four minutes; it has a
time limit of its own. Untraced, as `test_scan_cell.py`'s: a profile of
interpreted kernels takes minutes more to write and reduce (it was walked
once by hand, PERF.md, PR 40), and says nothing of the chip.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
CELL = "svc2fam-1w-closed3"
REHEARSAL_SECONDS = 900
NEW_METRICS = {
    "job_prepare_s_mean", "job_admit_ms_mean", "job_finish_ms_mean", "program_switch_share",
    "resident_geometry_MB", "resident_programs", "jobs_per_min",
}


def test_the_cell_and_its_metrics_find_their_files():
    assert manifest.validate(ROOT) == []
    listing = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert listing.returncode == 0 and listing.stderr == "" and CELL in listing.stdout
    cell = manifest.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.traffic["driver"] == "service" and cell.traffic["jobs_in_hand"] == 3
    benchmark = manifest.load_benchmark(ROOT)
    # the count is the manifest's own: every metric without a list, and those that list the cell
    wanted = {m["name"] for m in benchmark["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    names = {metric["name"] for metric in cell.per_layer}
    assert names == wanted and NEW_METRICS < names
    # the cell's own; the pool cell runs the same layers and took all but `jobs_per_min` in PR 44
    assert all(
        set(m["workloads"]) - {"svc2fam-4w-closed12"} == {CELL} for m in benchmark["per_layer"] if m["name"] in NEW_METRICS
    )
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}
    # the families are accepted configurations, read and not copied
    listed = {c["name"] for c in benchmark["configs"]}
    assert {family["config"] for family in cell.config["families"]} == {"04vs-14400f-1w", "03ph2scan-480f-1w"} < listed
    assert set(cell.config["sequence"]) == {family["family"] for family in cell.config["families"]}
    assert "render" not in cell.config and "check" in cell.config


def test_the_new_readers_find_nothing_in_a_program_without_the_series():
    """The parent's side of a traced run: no series, no value, no exception."""
    empty = {
        "window_s": 45.0, "workers": 1, "frames_per_s": 10.0, "files": [], "cache_entries_delta": 0,
        "render": {"width": 512, "height": 512, "samples": 7.6, "max_bounces": 4},
        "scrapes": {"master": ([{}], [{}]), "workers": ([{}], [{}])}, "trace": None, "jobs": [],
    }
    assert {readers.read_metric(name, empty, ROOT) for name in NEW_METRICS} == {None}


def test_the_new_readers_read_the_series_the_program_feeds():
    key = lambda name, **labels: (name, tuple(sorted(labels.items())))  # noqa: E731
    phase = lambda name, phase: key(f"sched_job_phase_seconds_{name}", phase=phase)  # noqa: E731
    master_before = {
        key("sched_jobs_finished_total"): 5.0,
        phase("sum", "admit_to_first_dispatch"): 40.0, phase("count", "admit_to_first_dispatch"): 5.0,
        phase("sum", "last_result_to_finished"): 0.1, phase("count", "last_result_to_finished"): 5.0,
    }
    master_after = {
        key("sched_jobs_finished_total"): 35.0,
        phase("sum", "admit_to_first_dispatch"): 40.9, phase("count", "admit_to_first_dispatch"): 35.0,
        phase("sum", "last_result_to_finished"): 0.85, phase("count", "last_result_to_finished"): 35.0,
    }
    worker_before = {key("worker_program_switches_total"): 10.0, key("worker_frame_phase_seconds_count", phase="render"): 100.0}
    worker_after = {
        key("worker_program_switches_total"): 60.0, key("worker_frame_phase_seconds_count", phase="render"): 600.0,
        key("worker_job_prepare_seconds_sum", family="04_very-simple"): 8.0,
        key("worker_job_prepare_seconds_count", family="04_very-simple"): 30.0,
        key("worker_job_prepare_seconds_sum", family="03_physics-2-scan"): 40.0,
        key("worker_job_prepare_seconds_count", family="03_physics-2-scan"): 10.0,
        key("render_resident_program_units"): 2.0,
        key("render_resident_geometry_bytes", family="03_physics-2-scan", space="hbm"): 71_303_168.0,
        key("render_resident_geometry_bytes", family="03_physics-2-scan", space="smem"): 65_504.0,
        key("render_resident_geometry_bytes", family="04_very-simple", space="hbm"): 0.0,
    }
    run = {"window_s": 45.0, "scrapes": {"master": ([master_before], [master_after]), "workers": ([worker_before], [worker_after])}}
    values = {name: readers.read_metric(name, run, ROOT) for name in NEW_METRICS}
    assert values == {
        "job_prepare_s_mean": 48.0 / 40.0, "job_admit_ms_mean": 1000.0 * (40.9 - 40.0) / 30.0,
        "job_finish_ms_mean": 1000.0 * 0.75 / 30.0, "program_switch_share": 10.0,
        "resident_geometry_MB": 71.368672, "resident_programs": 2.0, "jobs_per_min": 40.0,
    }


def test_a_whole_run_of_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "4000000111",
         "--seconds", "45", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 20
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    window = next(line for line in lines if line["stage"] == "window")
    assert all(family["files"] > 0 for family in window["families"].values())
    service = next(line for line in lines if line["stage"] == "service")
    assert service["problems"] == 0 and service["must"] > 100
