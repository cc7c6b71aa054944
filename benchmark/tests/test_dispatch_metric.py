"""`dispatch_on_event_share` as data: its file is found by its name, its
cells are cells of `BENCHMARK.json`, and its reader (the accepted
`delta_ratio`, from the master's scrape) returns nothing for a program
without the counter and the event share of the window's dispatches for
one with it."""

from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
METRIC = "dispatch_on_event_share"


def test_the_metric_finds_its_file_its_cells_and_its_series():
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == METRIC]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        "%", "higher", "program_counter", "master dispatch", "frames_per_s",
    )
    # its layer is one the accepted benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in benchmark["per_layer"] if m is not entry}
    cells = {w["name"] for w in benchmark["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    for name in entry["workloads"]:
        cell = manifest.load_cell(name, ROOT)
        assert METRIC in {m["name"] for m in cell.per_layer}
        assert entry["moves"] in {m["name"] for m in cell.end_to_end}
    # the other cells keep queues that never run shallow: it is not asked of them (the pool's
    # service loop is the one-worker service's, and took the metric in PR 44)
    for name in cells - set(entry["workloads"]):
        assert METRIC not in {m["name"] for m in manifest.load_cell(name, ROOT).per_layer}

    spec, directory = manifest.layer_metric_spec(METRIC, ROOT)
    assert spec["reader"] == "delta_ratio" and spec["from"] == "master" and spec["scale"] == 100.0
    assert not (directory / f"{METRIC}.py").exists(), "data, no reader code"
    assert spec["numerator"] == {"series": "master_dispatch_frames_total", "labels": {"trigger": "event"}}
    assert spec["denominator"] == {"series": "master_dispatch_frames_total"}

    key = lambda trigger: ("master_dispatch_frames_total", (("trigger", trigger),))  # noqa: E731
    run = {"scrapes": {"master": ([{}], [{}]), "workers": ([{}], [{}])}}
    assert readers.read_metric(METRIC, run, ROOT) is None  # the parent's side: no counter, no value, no exception
    run["scrapes"]["master"] = ([{key("event"): 0.0, key("tick"): 0.0}], [{key("event"): 0.0, key("tick"): 0.0}])
    assert readers.read_metric(METRIC, run, ROOT) is None  # nothing handed out in the window
    run["scrapes"]["master"] = ([{key("event"): 10.0, key("tick"): 8.0}], [{key("event"): 100.0, key("tick"): 18.0}])
    assert readers.read_metric(METRIC, run, ROOT) == 90.0
