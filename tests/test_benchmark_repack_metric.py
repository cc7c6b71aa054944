"""`repack_by_sort_share`, the metric of ISSUE 55, as the benchmark finds it.

The entry is the last of `per_layer`, names a layer the accepted benchmark
already has, lists the seven cells whose frames run the deep per-bounce
path (`_trace_paths_deep`) and none of the three that never enter it; its
file is data for the accepted `delta_ratio` reader, which gives nothing
for a program without the counter (the parent's side of this PR's check),
nothing for a window without a bounce launch, and 100 x sort / all
otherwise. Pure Python, no process started; in `tests/` because the
driver's tier-1 command collects `tests/` alone and this PR adds no code
under `benchmark/`.
"""

import subprocess
import sys
from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[1]
METRIC = "repack_by_sort_share"
DEEP_CELLS = [
    "03ph2mesh-1w-queued", "03ph2mesh-1w-fine", "03ph2scan-1w-queued", "03ph2assets-1w-queued",
    "svc2fam-1w-closed3", "svc2fam-4w-closed12", "svc2fam-4w-kill1",
]


def test_the_metric_finds_its_file_its_cells_and_its_series():
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == METRIC]
    # the 80th, where PR 55 appended it, with nothing before it come or gone
    assert benchmark["per_layer"][79] is entry and benchmark["per_layer"][78]["name"] == "process_stopped_s"
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "frames_per_s", "workloads": DEEP_CELLS,
    }
    assert entry["layer"] in {m["layer"] for m in benchmark["per_layer"] if m is not entry}
    for cell in benchmark["workloads"]:
        loaded = manifest.load_cell(cell["name"], ROOT)
        # the sphere megakernel's cells bypass the mechanism wholly, and do not report it
        assert (METRIC in {m["name"] for m in loaded.per_layer}) == (cell["name"] in DEEP_CELLS)
        assert entry["moves"] in {m["name"] for m in loaded.end_to_end}

    spec, directory = manifest.layer_metric_spec(METRIC, ROOT)
    assert not (directory / f"{METRIC}.py").exists(), "data, no reader code"
    assert {key: spec[key] for key in ("reader", "from", "scale", "numerator", "denominator")} == {
        "reader": "delta_ratio", "from": "workers", "scale": 100.0,
        "numerator": {"series": "render_bounce_repacks_total", "labels": {"by": "sort"}},
        "denominator": {"series": "render_bounce_repacks_total"},
    }


def test_the_reader_gives_nothing_without_the_counter_or_without_launches_and_the_share_with_both():
    by_sort = ("render_bounce_repacks_total", (("by", "sort"),))
    by_gather = ("render_bounce_repacks_total", (("by", "gather"),))
    frames = ("worker_frame_phase_seconds_count", (("phase", "render"),))
    run = {"scrapes": {"master": ([{}], [{}]), "workers": ([{frames: 10.0}], [{frames: 110.0}])}}
    assert readers.read_metric(METRIC, run, ROOT) is None  # the parent's side: no counter, no value, no exception
    run["scrapes"]["workers"] = ([{by_sort: 0.0, by_gather: 0.0}], [{by_sort: 0.0, by_gather: 0.0}])
    assert readers.read_metric(METRIC, run, ROOT) is None  # a fresh worker's zeros: no launch in the window
    # the mesh cells: every frame's four bounces at n, n, n/8, n/16
    run["scrapes"]["workers"] = ([{by_sort: 20.0, by_gather: 20.0}], [{by_sort: 220.0, by_gather: 220.0}])
    assert readers.read_metric(METRIC, run, ROOT) == 50.0
    # four workers: the pool's share of launches, not a mean of shares
    run["scrapes"]["workers"] = (
        [{by_sort: 0.0, by_gather: 0.0}] * 4,
        [{by_sort: 30.0, by_gather: 10.0}, {by_sort: 10.0, by_gather: 30.0}, {by_sort: 0.0, by_gather: 0.0}, {by_sort: 20.0, by_gather: 60.0}],
    )
    assert readers.read_metric(METRIC, run, ROOT) == 37.5


def test_the_list_prints_the_metric():
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--list"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    (line,) = [line for line in done.stdout.splitlines() if line.split()[:1] == [METRIC]]
    assert "layer: kernels" in line and "moves frames_per_s" in line and "program_counter" in line
