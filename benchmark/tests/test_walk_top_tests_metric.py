"""`walk_top_tests_per_entry`: its file is found by its name, it names the
kernels' layer beside `walk_top_step_share` and lists the two cells whose
every frame is the streamed walk, and its reader (a module in that metric's
form, from the workers' scrapes) returns nothing for a program without the
four counters or a window without an entry, and the top's steps for each
treelet entered otherwise: 4.4 on the counts a frame of the scan cell gave
under the binary top (PERF.md §5, PR 36), 1.4 on the wide top's."""

from pathlib import Path

import pytest

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
METRIC = "walk_top_tests_per_entry"
VISITS, LEAVES, ENTRIES, GROUPS = (
    ("render_walk_node_visits_total", ()), ("render_walk_leaf_tests_total", ()),
    ("render_walk_treelet_entries_total", ()), ("render_walk_group_tests_total", ()),
)


def test_the_metric_finds_its_file_its_layer_and_its_two_cells():
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == METRIC]
    # a new entry goes to the end of its list: after every entry the benchmark had (later PRs' come after it in turn)
    names = [m["name"] for m in benchmark["per_layer"]]
    assert names.index(METRIC) > names.index("dispatch_ahead_frame_share") > names.index("walk_top_step_share")
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        "count", "lower", "program_counter", "kernels", "frames_per_s",
    )
    (beside,) = [m for m in benchmark["per_layer"] if m["name"] == "walk_top_step_share"]
    assert entry["layer"] == beside["layer"] and entry["source"] == beside["source"]
    assert entry["workloads"] == ["03ph2scan-1w-queued", "03ph2assets-1w-queued"]
    for name in entry["workloads"]:
        cell = manifest.load_cell(name, ROOT)
        assert METRIC in {m["name"] for m in cell.per_layer}
        assert entry["moves"] in {m["name"] for m in cell.end_to_end}
    spec, directory = manifest.layer_metric_spec(METRIC, ROOT)
    assert spec["reader"] == "module" and (directory / f"{METRIC}.py").is_file()


def run_of(before: dict, after: dict, workers: int = 1) -> dict:
    return {"scrapes": {"master": ([{}], [{}]), "workers": ([before] * workers, [after] * workers)}}


@pytest.mark.parametrize("missing", [None, VISITS, LEAVES, ENTRIES, GROUPS, "no entry in the window"])
def test_the_reader_gives_nothing_without_the_counters_or_without_an_entry(missing):
    counts = {VISITS: 2_100_000.0, LEAVES: 493_056.0, ENTRIES: 222_400.0, GROUPS: 410_800.0}
    if missing is None:
        run = run_of({}, {})  # a program with no streamed walk: no series, no value, no exception
    elif missing == "no entry in the window":
        run = run_of(counts, counts)
    else:
        run = run_of({}, {series: value for series, value in counts.items() if series != missing})
    assert readers.read_metric(METRIC, run, ROOT) is None


@pytest.mark.parametrize("top, counts, reads", [
    # PERF.md §5 (PR 36), a frame of the scan cell under the binary top: 2.10 M steps, 493,056 + 410,800 + 222,400 inside
    ("binary", (2_100_000.0, 493_056.0, 222_400.0, 410_800.0), 4.4),
    # the same leaves and groups under a wide top that tests 1.4 nodes an entry and enters 3% more treelets
    ("wide", (1_453_616.0, 493_056.0, 229_070.0, 410_800.0), 1.4),
])
def test_the_reader_gives_the_tops_steps_for_each_treelet_entered(top, counts, reads):
    before = dict(zip((VISITS, LEAVES, ENTRIES, GROUPS), (7.0, 5.0, 3.0, 2.0)))  # an increase, not a total
    after = {series: before[series] + 30 * value for series, value in zip((VISITS, LEAVES, ENTRIES, GROUPS), counts)}
    value = readers.read_metric(METRIC, run_of(before, after), ROOT)
    assert value == pytest.approx(reads, abs=0.2 if top == "binary" else 0.01)
    # four workers: the pool's tests over the pool's entries
    assert readers.read_metric(METRIC, run_of(before, after, workers=4), ROOT) == pytest.approx(value)
