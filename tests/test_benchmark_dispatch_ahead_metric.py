"""`dispatch_ahead_frame_share`, counted in tier-1.

`benchmark/tests/test_dispatch_ahead_metric.py` (pure Python, no process
started) holds the metric's entry, its data file and the accepted
`delta_ratio` reader's three answers (no counter, no frames, a share). The
driver's tier-1 command collects `tests/` alone, and the metric is what
says on every ledger line whether dispatch-ahead engaged (PERF.md §3), so
its cases are brought in here under their own names, as
`tests/test_benchmark_backlog_rule.py` brings in the backlog rule.
"""

from benchmark.lib import manifest
from benchmark.tests.test_dispatch_ahead_metric import *  # noqa: F401,F403
from benchmark.tests.test_dispatch_ahead_metric import METRIC, ROOT


def test_the_metric_finds_its_file_its_cells_and_its_series():  # noqa: F811
    """The benchmark's case of this name, line for line on `BENCHMARK.json`
    as it is, but for one line. That case holds the entry to be the LAST of
    `per_layer`, where PR 50 put it, and the driver has every PR put its
    new entry last (one put in the middle reads as an edit of what was
    there, and refuses the PR): PR 51's `walk_top_tests_per_entry` follows
    it, so as committed that line fails, and only a `benchmark` PR may
    edit the benchmark's file (PERF.md §7 z). What the line was there to
    hold is held here by the entry's place: the 66th, with nothing before
    it come or gone, whatever later PRs append."""
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == METRIC]
    assert benchmark["per_layer"][65] is entry and benchmark["per_layer"][64]["name"] == "pool_frames_per_s_after_kill"
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        "%", "higher", "program_counter", "worker runtime", "frames_per_s",
    )
    assert entry["layer"] in {m["layer"] for m in benchmark["per_layer"] if m is not entry}
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
