"""The cell `04vs-1w-png`, counted in tier-1.

`benchmark/tests/test_png_cell.py` holds the cell to what ISSUE 52 names
(configuration, traffic, chips, the counts at PR 52: ten cells, three on
four chips, nine configurations with nine sources and files; held here by
the entries' places since PR 56 appended two cells and a configuration), to
differing from `04vs-1w-coarse` by the output format alone, its check to a
lossless file's limits, and its three metrics to being data whose reader
gives nothing for a program without their series. The driver's tier-1
command collects `tests/` alone, so those cases (pure Python, but for one
`run.py --list`) are brought in here under their own names, as
`tests/test_benchmark_dispatch_ahead_metric.py` brings in its. The cell's
rehearsal starts processes and stays where it is, outside tier-1, as the
other cells' rehearsals do.
"""

import os
import subprocess
import sys
from pathlib import Path

from benchmark.drivers import backlog
from benchmark.lib import manifest
from benchmark.tests.test_png_cell import (  # noqa: F401
    CELL,
    JPEG_CELL,
    NEW_METRICS,
    ROOT,
    test_the_check_reads_a_lossless_file_without_a_codec_and_tighter_than_jpegs,
    test_the_readers_give_nothing_for_a_program_without_the_series_and_the_value_with_them,
)

# the ten cells of PR 52, in their order: what `workloads` began with then and begins with since
CELLS_AT_PR_52 = [
    "04vs-1w-coarse", "04vs-4w-batch", "03ph2mesh-1w-queued", "03ph2mesh-1w-fine", "03ph2scan-1w-queued",
    "03ph2assets-1w-queued", "svc2fam-1w-closed3", "svc2fam-4w-closed12", "svc2fam-4w-kill1", CELL,
]


def test_the_cell_is_data_and_says_what_the_issue_says():
    """The benchmark's case of this name, line for line, but for where it
    holds the counts of PR 52 (ten cells, nine configurations) and the
    cell and its configuration to be the LAST of their lists: later PRs
    append behind them (PR 56: two cells and a configuration), so the entry
    is held by its place, the tenth cell and the ninth configuration with
    nothing before them come or gone, and the counts by `BENCHMARK.json`
    as it stands."""
    assert manifest.validate(ROOT) == []
    listing = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert listing.returncode == 0 and listing.stderr == ""
    assert "04vs-1w-png            config 04vs-14400f-1w-png   traffic backlog-coarse100    chips 1" in listing.stdout
    benchmark = manifest.load_benchmark(ROOT)
    assert [w["name"] for w in benchmark["workloads"][:10]] == CELLS_AT_PR_52
    assert sum(w["chips"] == 4 for w in benchmark["workloads"][:10]) == 3
    configs = benchmark["configs"]
    assert len({c["source"] for c in configs}) == len({c["file"] for c in configs}) == len(configs) >= 9
    assert benchmark["workloads"][9]["name"] == CELL and configs[8]["name"] == "04vs-14400f-1w-png"
    entry = configs[8]
    assert entry["reduced"] == ["frame_range_from"] and len(entry["source"]) <= 200
    assert "04_very-simple_demo_60f-1w.toml" in entry["source"] and "14400f-1w" in entry["source"]
    cell = manifest.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.config_name == "04vs-14400f-1w-png"
    assert cell.traffic["name"] == "backlog-coarse100" and cell.traffic["driver"] == "backlog"
    assert cell.traffic["strategy"] == {"strategy_type": "eager-naive-coarse", "target_queue_size": 100}
    assert cell.traffic["warmup_frames_per_worker"] == 3
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}


def test_the_two_04vs_one_worker_cells_differ_by_the_output_format_alone():
    """The benchmark's case of this name, line for line, but for where it
    holds PR 52's three metrics to be the last three of the cell's
    (`test_the_three_readers_...` below has why): they are the three before
    PR 53's one, in both cells."""
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
    # the same metrics, PR 52's three and PR 53's one in both: those four directly before the eight
    # that PR 54 appended to every cell's list, whatever later PRs append behind them
    assert [m["name"] for m in png.per_layer] == [m["name"] for m in jpeg.per_layer]
    names = [m["name"] for m in png.per_layer]
    first_of_pr54 = names.index("host_cpu_ms_per_frame")
    assert names[first_of_pr54 - 4:first_of_pr54] == [*NEW_METRICS, "save_beside_save_frame_share"]
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


def test_the_three_readers_are_data_and_read_the_series_the_issue_names():
    """The benchmark's case of this name, line for line on `BENCHMARK.json`
    as it is, but for its counts. That case holds PR 52's three entries to
    be the LAST three of `per_layer` and the lists that name the JPEG cell
    to be 14 + 3, and the driver has every PR put its new entry last: PR
    53's `save_beside_save_frame_share` follows them and names the JPEG
    cell too, so as committed those two lines fail, and only a `benchmark`
    PR may edit the benchmark's file (PERF.md §7). What they were there to
    hold is held here by the entries' places: the 68th to the 70th, after
    `walk_top_tests_per_entry`, with nothing before them come or gone,
    whatever later PRs append (PR 54 appended eight that name both cells,
    so PR 53's entry is held by its place too: the 71st)."""
    benchmark = manifest.load_benchmark(ROOT)
    entries = benchmark["per_layer"][67:70]
    assert [m["name"] for m in entries] == list(NEW_METRICS)
    assert benchmark["per_layer"][66]["name"] == "walk_top_tests_per_entry"
    for entry in entries:
        # (its two cells first; `04vs-1w-fine`, PR 56's, runs the JPEG cell's job and follows them)
        assert (entry["layer"], entry["moves"], entry["workloads"][:2]) == ("result plane", "frames_per_s", [JPEG_CELL, CELL])
        assert not set(entry["workloads"][2:]) & set(CELLS_AT_PR_52)
        spec, directory = manifest.layer_metric_spec(entry["name"], ROOT)
        assert spec["reader"] == "delta_ratio" and spec["from"] == "workers"
        assert not (directory / f"{entry['name']}.py").exists(), "data, no reader code"
    assert [(m["unit"], m["better"]) for m in entries] == [("MB/s", "higher"), ("ms", "lower"), ("%", "lower")]
    # every list that named the JPEG cell at PR 52 names the new cell too, last of the cells there were
    # then (a later PR's cells follow it: PR 56's `04vs-1w-fine`); PR 53's does as well
    named = [m for m in benchmark["per_layer"][:70] if JPEG_CELL in m.get("workloads", [])]
    assert len(named) == 14 + 3
    assert all([w for w in m["workloads"] if w in CELLS_AT_PR_52][-1] == CELL for m in named)
    # (the 71st entry alone: the entries behind it are later PRs' own, and PR 54's eight name every cell)
    later = benchmark["per_layer"][70]
    assert later["name"] == "save_beside_save_frame_share" and JPEG_CELL in later["workloads"]
    assert [w for w in later["workloads"] if w in CELLS_AT_PR_52][-1] == CELL
