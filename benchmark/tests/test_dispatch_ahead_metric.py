"""`dispatch_ahead_frame_share` as data: its file is found by its name, it
names a layer the benchmark already has and lists every cell (every cell
runs the worker's frame loop), and its reader (the accepted `delta_ratio`,
from the workers' scrapes) returns nothing for a program without the
counter, nothing for a window without frames, and 100 x the share of the
window's frames that were issued ahead for one with both."""

from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
METRIC = "dispatch_ahead_frame_share"


def test_the_metric_finds_its_file_its_cells_and_its_series():
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == METRIC]
    assert benchmark["per_layer"][-1] is entry  # a new entry goes to the end of its list
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        "%", "higher", "program_counter", "worker runtime", "frames_per_s",
    )
    # its layer is one the accepted benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in benchmark["per_layer"] if m is not entry}
    # every cell goes through the one loop, the cell that bypasses the mechanism too (it reads about 0 there)
    assert entry["workloads"] == [w["name"] for w in benchmark["workloads"]]
    for name in entry["workloads"]:
        cell = manifest.load_cell(name, ROOT)
        assert METRIC in {m["name"] for m in cell.per_layer}
        assert entry["moves"] in {m["name"] for m in cell.end_to_end}

    spec, directory = manifest.layer_metric_spec(METRIC, ROOT)
    assert spec["reader"] == "delta_ratio" and spec["from"] == "workers" and spec["scale"] == 100.0
    assert not (directory / f"{METRIC}.py").exists(), "data, no reader code"
    assert spec["numerator"] == {"series": "worker_frames_issued_ahead_total"}
    assert spec["denominator"] == {"series": "worker_frame_phase_seconds_count", "labels": {"phase": "render"}}


def test_the_reader_gives_nothing_without_the_counter_or_without_frames_and_the_share_with_both():
    ahead = ("worker_frames_issued_ahead_total", ())
    frames = ("worker_frame_phase_seconds_count", (("phase", "render"),))
    other = ("worker_frame_phase_seconds_count", (("phase", "write"),))
    run = {"scrapes": {"master": ([{}], [{}]), "workers": ([{frames: 10.0}], [{frames: 110.0}])}}
    assert readers.read_metric(METRIC, run, ROOT) is None  # the parent's side: no counter, no value, no exception
    run["scrapes"]["workers"] = ([{ahead: 0.0, frames: 10.0}], [{ahead: 0.0, frames: 10.0}])
    assert readers.read_metric(METRIC, run, ROOT) is None  # no frame in the window
    run["scrapes"]["workers"] = ([{ahead: 9.0, frames: 10.0, other: 10.0}], [{ahead: 99.0, frames: 110.0, other: 400.0}])
    assert readers.read_metric(METRIC, run, ROOT) == 90.0
    # four workers: the pool's share, not a mean of shares
    run["scrapes"]["workers"] = (
        [{ahead: 0.0, frames: 0.0}] * 4,
        [{ahead: 100.0, frames: 100.0}, {ahead: 50.0, frames: 100.0}, {ahead: 0.0, frames: 100.0}, {ahead: 50.0, frames: 100.0}],
    )
    assert readers.read_metric(METRIC, run, ROOT) == 50.0
