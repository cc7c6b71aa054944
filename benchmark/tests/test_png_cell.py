"""The cell `04vs-1w-png` as data: it finds its files, says what ISSUE 52
says, differs from `04vs-1w-coarse` by the output format alone, its three
metrics find their readers (the accepted `delta_ratio`, as data) and give
nothing for a program without their series, and a whole run of it walks
through on the CPU.

The rehearsal starts a master and a worker as real processes at 64x64
through the Pallas interpreter (about a minute); it has a time limit of its
own and is not part of tier-1: `tests/test_benchmark_png_cell.py` brings
the other cases in by name.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.drivers import backlog
from benchmark.lib import check, manifest, readers

ROOT = Path(__file__).resolve().parents[2]
CELL, JPEG_CELL = "04vs-1w-png", "04vs-1w-coarse"
REHEARSAL_SECONDS = 600
NEW_METRICS = ("encode_MB_per_s", "held_ms_per_frame", "save_bound_share")


def key(name: str, **labels: str):
    return (name, tuple(sorted(labels.items())))


def test_the_cell_is_data_and_says_what_the_issue_says():
    assert manifest.validate(ROOT) == []
    listing = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert listing.returncode == 0 and listing.stderr == ""
    assert "04vs-1w-png            config 04vs-14400f-1w-png   traffic backlog-coarse100    chips 1" in listing.stdout
    benchmark = manifest.load_benchmark(ROOT)
    # the counts from this PR on: ten cells, three of them on four chips, nine configurations
    assert len(benchmark["workloads"]) == 10 and sum(w["chips"] == 4 for w in benchmark["workloads"]) == 3
    assert len(benchmark["configs"]) == 9
    assert len({c["source"] for c in benchmark["configs"]}) == len({c["file"] for c in benchmark["configs"]}) == 9
    assert benchmark["workloads"][-1]["name"] == CELL and benchmark["configs"][-1]["name"] == "04vs-14400f-1w-png"
    entry = benchmark["configs"][-1]
    assert entry["reduced"] == ["frame_range_from"] and len(entry["source"]) <= 200
    assert "04_very-simple_demo_60f-1w.toml" in entry["source"] and "14400f-1w" in entry["source"]
    cell = manifest.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.config_name == "04vs-14400f-1w-png"
    assert cell.traffic["name"] == "backlog-coarse100" and cell.traffic["driver"] == "backlog"
    assert cell.traffic["strategy"] == {"strategy_type": "eager-naive-coarse", "target_queue_size": 100}
    assert cell.traffic["warmup_frames_per_worker"] == 3
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}


def test_the_two_04vs_one_worker_cells_differ_by_the_output_format_alone():
    png, jpeg = manifest.load_cell(CELL, ROOT), manifest.load_cell(JPEG_CELL, ROOT)
    assert png.traffic == jpeg.traffic
    for kept in ("render", "frames", "workers", "trace_slice_s", "frame_range_from", "holds_frames_per_s", "reduced"):
        assert png.config[kept] == jpeg.config[kept], kept
    assert png.config["deployment"]["scene_family"] == jpeg.config["deployment"]["scene_family"] == "04_very-simple"
    assert png.config["output"] == {"file_format": "PNG", "file_name_format": "rendered-######", "extension": ".png"}
    assert jpeg.config["output"]["file_format"] == "JPEG" and jpeg.config["output"]["jpeg_quality"] == 90
    # the guarantees: the JPEG configuration's three, word for word, and the fourth
    assert {k: v for k, v in png.config["guarantees"].items() if k in jpeg.config["guarantees"]} == jpeg.config["guarantees"]
    assert set(png.config["guarantees"]) - set(jpeg.config["guarantees"]) == {"the_file_is_the_programs_pixels"}
    assert {"output_format_on_the_measuring_job", "bit_depth_and_compression", "render"} == set(png.config["assumed"])
    # the same metrics, and the three of this PR in both
    assert [m["name"] for m in png.per_layer] == [m["name"] for m in jpeg.per_layer]
    assert [m["name"] for m in png.per_layer][-3:] == list(NEW_METRICS)
    # the job files: one line of the job itself differs (its name and description besides)
    png_lines, jpeg_lines = (
        set((cell.config_dir / cell.config["job_template"]).read_text().splitlines()) for cell in (png, jpeg)
    )
    differing = {line.split(" = ")[0] for line in png_lines ^ jpeg_lines if not line.startswith("#")}
    assert differing == {"job_name", "job_description", "output_file_format"}
    with_seed = {}
    for cell in (png, jpeg):
        with_seed[cell.name] = backlog.render_job_file(cell, 5200001212, Path(os.devnull))
    assert with_seed[CELL][1:] == with_seed[JPEG_CELL][1:]  # the same seed draws the same frames
    assert with_seed[CELL][0] == "04vs_measuring_14400f-1w-png"


def test_the_check_reads_a_lossless_file_without_a_codec_and_tighter_than_jpegs():
    png, jpeg = manifest.load_cell(CELL, ROOT).config["check"], manifest.load_cell(JPEG_CELL, ROOT).config["check"]
    same, independent, frames = png["same_stream"], png["independent"], png["frames"]
    assert same["crops"] == jpeg["same_stream"]["crops"] and (same["crop"], same["border"]) == (96, 16)
    assert same["max_levels"] < jpeg["same_stream"]["max_levels"] == 8
    assert same["min_share"] >= jpeg["same_stream"]["min_share"]
    assert independent["reference"] == "plain_tracer" and independent["crops"] == jpeg["independent"]["crops"]
    assert independent["abs_levels"] < jpeg["independent"]["abs_levels"] == 2.5  # no DC quantisation to allow for
    assert (independent["sigmas"], independent["replicas"], independent["block"]) == (5.0, 16, 16)
    # both checked frames early in a window at the PNG cell's rate, and references that still hit the cache
    assert frames["after"] < jpeg["frames"]["after"] == 400 and frames["after"] % frames["quantum"] == 0
    assert (frames["count"], frames["step"], frames["quantum"]) == (2, 4, 16)
    span = manifest.load_cell(CELL, ROOT).config["frame_range_from"]["span"]
    pairs = {tuple(check.checked_frames(first, 14400, frames)) for first in range(1, span + 1)}
    assert len(pairs) == 45 and all(b - a == 4 and a % 16 == 0 for a, b in pairs)
    assert all(len(spec["why"]) > 200 for spec in (same, independent, frames))  # each limit with its reason


def test_the_three_readers_are_data_and_read_the_series_the_issue_names():
    benchmark = manifest.load_benchmark(ROOT)
    entries = benchmark["per_layer"][-3:]
    assert [m["name"] for m in entries] == list(NEW_METRICS)  # at the end, after walk_top_tests_per_entry
    assert benchmark["per_layer"][-4]["name"] == "walk_top_tests_per_entry"
    for entry in entries:
        assert (entry["layer"], entry["moves"], entry["workloads"]) == ("result plane", "frames_per_s", [JPEG_CELL, CELL])
        spec, directory = manifest.layer_metric_spec(entry["name"], ROOT)
        assert spec["reader"] == "delta_ratio" and spec["from"] == "workers"
        assert not (directory / f"{entry['name']}.py").exists(), "data, no reader code"
    assert [(m["unit"], m["better"]) for m in entries] == [("MB/s", "higher"), ("ms", "lower"), ("%", "lower")]
    # every accepted list that names the JPEG cell names the new cell too, last
    named = [m for m in benchmark["per_layer"] if JPEG_CELL in m.get("workloads", [])]
    assert len(named) == 14 + 3 and all(m["workloads"][-1] == CELL for m in named)


def test_the_readers_give_nothing_for_a_program_without_the_series_and_the_value_with_them():
    frames = key("worker_frame_phase_seconds_count", phase="render")
    encode = key("worker_frame_step_seconds_sum", step="encode")
    states = {state: key("worker_loop_seconds_total", state=state) for state in ("no_work", "render_call", "report", "save_wait")}
    # the parent's program: steps and loop states, no byte counter and no hold
    before = {frames: 10.0, encode: 1.0, **{series: 0.0 for series in states.values()}}
    after = {frames: 460.0, encode: 41.0, states["no_work"]: 0.0, states["render_call"]: 4.0, states["report"]: 1.0,
             states["save_wait"]: 40.0}
    run = {"scrapes": {"master": ([{}], [{}]), "workers": ([before], [after])}}
    assert readers.read_metric("encode_MB_per_s", run, ROOT) is None
    assert readers.read_metric("held_ms_per_frame", run, ROOT) is None
    # the state is the parent's own (PR 45), so its side of the pair has this one
    assert abs(readers.read_metric("save_bound_share", run, ROOT) - 100.0 * 40.0 / 45.0) < 1e-9
    # a program without the loop's states at all: nothing, and no exception
    bare = {"scrapes": {"master": ([{}], [{}]), "workers": ([{frames: 10.0}], [{frames: 460.0}])}}
    assert {readers.read_metric(name, bare, ROOT) for name in NEW_METRICS} == {None}
    # this PR's program
    pixels, held_sum, held_count = key("worker_frame_pixel_bytes_total"), key("worker_frame_held_seconds_sum"), key("worker_frame_held_seconds_count")
    before.update({pixels: 0.0, held_sum: 0.0, held_count: 0.0})
    after.update({pixels: 450 * 786432.0, held_sum: 72.0, held_count: 450.0})
    assert abs(readers.read_metric("encode_MB_per_s", run, ROOT) - 450 * 0.786432 / 40.0) < 1e-9
    assert abs(readers.read_metric("held_ms_per_frame", run, ROOT) - 160.0) < 1e-9
    # a window with no frame and no encode time: nothing to divide by
    still = {"scrapes": {"master": ([{}], [{}]), "workers": ([after], [after])}}
    assert {readers.read_metric(name, still, ROOT) for name in NEW_METRICS} == {None}


def test_a_whole_run_of_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "5200001212",
         "--seconds", "20", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 20
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    checked = next(line for line in lines if line["stage"] == "check")
    assert checked["problems"] == [] and set(checked["same_stream"]["agreement"].values()) == {1.0}
