"""`save_beside_save_frame_share` (PR 53), counted in tier-1.

The metric that says on a ledger line how often more than one save thread
is at work: its entry is the LAST of `per_layer`, with nothing before it
come or gone; its file is data that the accepted `delta_ratio` reader
reads (no reader code); its three cells are found and report the
end-to-end metric it moves; and the reader gives nothing for a program
without the counter (the parent's side of this PR's comparison), nothing
for a window without frames, and the pool's share for one with both. Held
here, in `tests/`, because the driver's tier-1 command collects `tests/`
alone and a PR that claims a gain adds no code under the benchmark's
paths (`tests/test_benchmark_dispatch_ahead_metric.py` has the same
reason).
"""

from pathlib import Path

from benchmark.lib import manifest, readers
from tpu_render_cluster.obs import MetricsRegistry, Tracer
from tpu_render_cluster.traces.worker_trace import WorkerTraceBuilder
from tpu_render_cluster.utils.cancellation import CancellationToken

ROOT = Path(__file__).resolve().parents[1]
METRIC = "save_beside_save_frame_share"
# what `per_layer` held before PR 53, in its order: 70 entries, the last three PR 52's
BEFORE = 70
CELLS = ["04vs-1w-coarse", "04vs-4w-batch", "04vs-1w-png"]
LATER_CELLS = ["04vs-1w-fine"]  # appended by PR 56 to every list that names `04vs-1w-coarse`
DECLARED_BEFORE = 69  # `TRC_*` names `utils/env.py` declared at PR 52


def test_the_entry_is_the_last_of_per_layer_and_nothing_before_it_has_come_or_gone():
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    names = [m["name"] for m in benchmark["per_layer"]]
    assert names.index(METRIC) == BEFORE and len(names) == len(set(names))
    assert names[BEFORE - 3:BEFORE] == ["encode_MB_per_s", "held_ms_per_frame", "save_bound_share"]
    assert names[65] == "dispatch_ahead_frame_share" and names[66] == "walk_top_tests_per_entry"
    entry = benchmark["per_layer"][BEFORE]
    # its three cells first, as PR 53 listed them; behind them the cells later PRs added that run
    # a `04vs` one-worker job (PR 56: `04vs-1w-fine`, where one save at a time leaves it at 0)
    assert entry["workloads"][:3] == CELLS and entry["workloads"][3:] == LATER_CELLS
    assert {key: value for key, value in entry.items() if key != "workloads"} == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "result plane", "moves": "frames_per_s",
    }
    # its layer is one the accepted benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in benchmark["per_layer"][:BEFORE]}


def test_the_metric_finds_its_file_its_three_cells_and_its_series():
    benchmark = manifest.load_benchmark(ROOT)
    cells = {w["name"]: w for w in benchmark["workloads"]}
    for name in CELLS:
        cell = manifest.load_cell(name, ROOT)
        assert METRIC in {m["name"] for m in cell.per_layer}
        assert "frames_per_s" in {m["name"] for m in cell.end_to_end}
    # the pair made for the comparison (the same frames, PNG and JPEG) and the pool that runs two encoders a process
    assert [cells[name]["chips"] for name in CELLS] == [1, 4, 1]
    for name in set(cells) - set(CELLS) - set(LATER_CELLS):
        assert METRIC not in {m["name"] for m in manifest.load_cell(name, ROOT).per_layer}
    spec, directory = manifest.layer_metric_spec(METRIC, ROOT)
    assert spec["reader"] == "delta_ratio" and spec["from"] == "workers" and spec["scale"] == 100.0
    assert not (directory / f"{METRIC}.py").exists(), "data, no reader code"
    assert spec["numerator"] == {"series": "worker_frames_saved_beside_save_total"}
    assert spec["denominator"] == {"series": "worker_frame_phase_seconds_count", "labels": {"phase": "render"}}
    assert set(spec) == {"reader", "from", "numerator", "denominator", "scale", "what"}


def test_the_reader_gives_nothing_without_the_counter_or_without_frames_and_the_share_with_both():
    beside = ("worker_frames_saved_beside_save_total", ())
    frames = ("worker_frame_phase_seconds_count", (("phase", "render"),))
    other = ("worker_frame_phase_seconds_count", (("phase", "write"),))
    run = {"scrapes": {"master": ([{}], [{}]), "workers": ([{frames: 10.0}], [{frames: 110.0}])}}
    assert readers.read_metric(METRIC, run, ROOT) is None  # the parent's side: no counter, no value, no exception
    run["scrapes"]["workers"] = ([{beside: 0.0, frames: 10.0}], [{beside: 0.0, frames: 10.0}])
    assert readers.read_metric(METRIC, run, ROOT) is None  # no frame in the window
    run["scrapes"]["workers"] = ([{beside: 0.0, frames: 10.0}], [{beside: 0.0, frames: 510.0}])
    assert readers.read_metric(METRIC, run, ROOT) == 0.0  # the JPEG cell: the counter is there and stays at 0
    run["scrapes"]["workers"] = ([{beside: 9.0, frames: 10.0, other: 10.0}], [{beside: 108.0, frames: 110.0, other: 400.0}])
    assert readers.read_metric(METRIC, run, ROOT) == 99.0
    # four workers: the pool's share, not a mean of shares
    run["scrapes"]["workers"] = (
        [{beside: 0.0, frames: 0.0}] * 4,
        [{beside: 100.0, frames: 100.0}, {beside: 20.0, frames: 100.0}, {beside: 0.0, frames: 100.0}, {beside: 0.0, frames: 100.0}],
    )
    assert readers.read_metric(METRIC, run, ROOT) == 30.0


def test_the_series_the_file_names_is_the_one_the_worker_exposes_from_its_start():
    """The data file and the program agree on the counter's name, and a
    worker that has rendered nothing has it at 0 (a scrape that found no
    series could not tell "never happened" from "not counted")."""
    import asyncio

    from tpu_render_cluster.worker.backends.mock import MockBackend
    from tpu_render_cluster.worker.queue import WorkerAutomaticQueue

    spec, _ = manifest.layer_metric_spec(METRIC, ROOT)
    registry = MetricsRegistry()

    async def made() -> None:
        WorkerAutomaticQueue(
            MockBackend(), None, WorkerTraceBuilder(), CancellationToken(),
            metrics=registry, span_tracer=Tracer("worker-metric-test"),
        )

    asyncio.run(made())
    snapshot = registry.snapshot()
    assert snapshot[spec["numerator"]["series"]]["series"] == {"": 0.0}
    assert spec["denominator"]["series"].removesuffix("_count") in {"worker_frame_phase_seconds"}


def test_no_option_selects_the_number_of_save_threads():
    """`SAVE_FRAMES` is a constant of the loop: `utils/env.py` declares as
    many `TRC_*` names as before PR 53 and none of them names a save."""
    from tpu_render_cluster.utils import env
    from tpu_render_cluster.worker import queue

    names = list(env.ENV_VARS)
    assert len(names) == DECLARED_BEFORE
    assert not [name for name in names if "SAVE" in name.upper()]
    assert queue.SAVE_FRAMES == 8 and isinstance(queue.SAVE_FRAMES, int)
    source = Path(queue.__file__).read_text()
    assert "environ" not in source and "TRC_" not in source
