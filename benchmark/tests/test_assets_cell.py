"""The cell `03ph2assets-1w-queued` as data: it finds its files, its metrics
find their readers, and a whole run of it walks through on the CPU.

The rehearsal starts a master and a worker as real processes, builds the
three real BLASes (1.3 million triangles, a few seconds a process) and
renders 64x64 frames of the real scene through the Pallas interpreter; the
check's children and its reference build them again, so it takes several
minutes and has a time limit of its own. Untraced, as the scan cell's: a
profile of interpreted kernels is not written inside the harness's limit.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
CELL = "03ph2assets-1w-queued"
REHEARSAL_SECONDS = 1500
# first frame 296: the checked frames are 304 and 308, twelve and sixteen frames in, so a slow
# host still has them on disk when the window ends
SEED = "3200000222"
NEW_METRICS = {"geometry_blas_count", "walk_top_step_share", "bvh_build_s"}
SCAN_METRICS = {"treelet_fetch_MB_per_frame", "walk_node_visits_per_ray", "geometry_hbm_MB", "walk_hbm_roofline_share"}


def key(name, **labels):
    return (name, tuple(sorted(labels.items())))


def test_the_cell_and_its_metrics_find_their_files():
    assert manifest.validate(ROOT) == []
    cell = manifest.load_cell(CELL, ROOT)
    deployment = cell.config["deployment"]
    assert cell.chips == 1 and deployment["bodies"] == 48 and len(deployment["models"]) == 3
    assert sum(model["generated_triangles"] for model in deployment["models"]) == deployment["triangles"] == 1_286_504
    assert [model["bodies"] for model in deployment["models"]] == [list(range(m, 48, 3)) for m in range(3)]
    assert sum(model["treelet_slabs"] for model in deployment["models"]) * 69_632 == deployment["tables"]["hbm_bytes"]
    assert cell.config["render"]["samples"] == 1
    assert cell.traffic["strategy"]["strategy_type"] == "tpu-batch"
    names = {metric["name"] for metric in cell.per_layer}
    # the scan cell's under their accepted names, and the three this configuration brought; the
    # count is the manifest's own: every metric without a list, and those that list the cell
    assert names == {m["name"] for m in manifest.load_cell("03ph2scan-1w-queued", ROOT).per_layer} | NEW_METRICS
    benchmark = manifest.load_benchmark(ROOT)
    wanted = {m["name"] for m in benchmark["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    assert NEW_METRICS | SCAN_METRICS < names and names == wanted
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}
    assert cell.config["check"]["independent"]["reference"] == "plain_tracer_assets"
    assert set(cell.config["reduced"]) == {"workers", "frame_range_from", "samples", "models"}
    # the scan and assets configurations differ by the asset set alone
    scan = manifest.load_cell("03ph2scan-1w-queued", ROOT).config
    for same in ("render", "output", "guarantees", "frame_range_from", "frames", "trace_slice_s"):
        assert {k: v for k, v in cell.config[same].items() if k != "why"} == {
            k: v for k, v in scan[same].items() if k != "why"
        } if isinstance(scan[same], dict) else cell.config[same] == scan[same]


def test_the_new_readers_find_nothing_in_a_program_without_the_series():
    """The parent's side of a traced run: no series, no value, and no
    exception."""
    empty = {
        "window_s": 45.0, "workers": 1, "frames_per_s": 1.0, "files": [], "cache_entries_delta": 0,
        "render": {"width": 512, "height": 512, "samples": 1, "max_bounces": 4},
        "scrapes": {"master": ([{}], [{}]), "workers": ([{}], [{}])}, "trace": None,
    }
    assert {readers.read_metric(name, empty, ROOT) for name in NEW_METRICS} == {None}
    # the parent has the walk's steps and leaf tests and not the two counters that split the rest
    parent = {key("render_walk_node_visits_total"): 100.0, key("render_walk_leaf_tests_total"): 20.0}
    run = {**empty, "scrapes": {"workers": ([{}], [parent])}}
    assert readers.read_metric("walk_top_step_share", run, ROOT) is None


def test_the_new_readers_read_the_workers_series():
    before = {
        key("render_walk_node_visits_total"): 1000.0, key("render_walk_leaf_tests_total"): 100.0,
        key("render_walk_treelet_entries_total"): 50.0, key("render_walk_group_tests_total"): 50.0,
    }
    after = {
        key("render_walk_node_visits_total"): 3000.0, key("render_walk_leaf_tests_total"): 560.0,
        key("render_walk_treelet_entries_total"): 250.0, key("render_walk_group_tests_total"): 390.0,
        key("render_geometry_blas_units"): 3.0,
        key("render_bvh_build_seconds", model="bunny"): 0.25, key("render_bvh_build_seconds", model="dragon"): 19.5,
        key("render_bvh_build_seconds", model="upload"): 2.25,
    }
    run = {"scrapes": {"workers": ([before], [after])}}
    assert readers.read_metric("geometry_blas_count", run, ROOT) == 3.0
    assert readers.read_metric("bvh_build_s", run, ROOT) == 22.0
    # of 2,000 steps 460 + 200 + 340 were inside treelets: half were the top's
    assert readers.read_metric("walk_top_step_share", run, ROOT) == 50.0


def test_a_whole_run_of_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", SEED,
         "--seconds", "45", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 20
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
