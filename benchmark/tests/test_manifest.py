"""The manifest: names, units, and every cell's files found by name."""

import json

import pytest

from benchmark.lib import manifest, readers
from benchmark.lib.peaks import chip_peaks

BENCHMARK = manifest.load_benchmark()
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCHMARK) == KEYS
    assert len(json.dumps(BENCHMARK)) < 64 * 1024
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert manifest.validate() == []


@pytest.mark.parametrize("name,ok", [
    ("04vs-1w-coarse", True), ("kernel_Mpaths_per_s", True), ("_x.y-z", True),
    ("has space", False), ("a/b", False), ("a,b", False), ("-lead", False), ("x" * 65, False), ("", False),
])
def test_names_are_letters_digits_and_three_marks(name, ok):
    assert bool(manifest.NAME_RE.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("frames/s", True), ("%", True), ("Mpaths/s", True), ("ms", True),
    ("tokens per second", False), ("µs", False), ("x" * 17, False), ("", False),
])
def test_units_are_short_and_have_no_space(unit, ok):
    assert bool(manifest.UNIT_RE.match(unit)) is ok


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_finds_its_files_by_name(cell_name):
    cell = manifest.load_cell(cell_name)
    # the driver is the one the traffic file names, and the job templates are where it looks for them
    assert (manifest.BENCH_DIR / "drivers" / f"{cell.traffic['driver']}.py").is_file()
    if cell.traffic["driver"] == "backlog":
        assert (cell.config_dir / cell.config["job_template"]).is_file()
    else:  # a service configuration names accepted configurations and reads their templates
        listed = {c["name"]: c for c in BENCHMARK["configs"]}
        for family in cell.config["families"]:
            directory = (manifest.ROOT / listed[family["config"]]["file"]).parent
            assert (directory / "job.toml.template").is_file()
    assert cell.config["workers"] == cell.chips
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s", "setup_s"}
    for metric in cell.per_layer:
        spec, directory = manifest.layer_metric_spec(metric["name"])
        assert spec["reader"] in ("delta", "delta_ratio", "module")


def test_entries_have_just_the_contracts_keys():
    for config in BENCHMARK["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    for workload in BENCHMARK["workloads"]:
        assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    for metric in BENCHMARK["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for metric in BENCHMARK["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_declarative_readers_take_deltas_and_return_nothing_for_nothing():
    key = ("worker_frame_phase_seconds_sum", (("phase", "write"),))
    count = ("worker_frame_phase_seconds_count", (("phase", "write"),))
    run = {"scrapes": {"workers": ([{key: 1.0, count: 10.0}], [{key: 1.6, count: 40.0}]), "master": ([{}], [{}])}}
    assert readers.read_metric("save_ms_per_frame", run) == pytest.approx(20.0)
    assert readers.read_metric("assign_ms_mean", run) is None


def test_an_unknown_chip_is_an_error_not_a_default():
    assert chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        chip_peaks("TPU v9 imaginary")
