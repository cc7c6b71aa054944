"""The benchmark's data: BENCHMARK.json and the files it names.

`BENCHMARK.json` is the list of cells and metrics. Everything that belongs
to one configuration, one traffic mix or one per-layer metric sits in a
file of its own that is found by the name written there:

- configuration `<c>`:   the `file` of its entry (`configs/<c>/config.json`)
  with the job template beside it;
- traffic mix `<t>`:      `traffic/<t>.json`;
- per-layer metric `<m>`: `layer_metrics/<m>.json`, and `<m>.py` beside it
  where the reader is code of its own.

A later PR adds a cell by adding such files and entries; no file that is
there needs an edit.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


@dataclass(frozen=True)
class Cell:
    """One entry of `workloads` with the data files it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict
    config_dir: Path
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _bench_dir(benchmark: dict, root: Path) -> Path:
    return root / benchmark["paths"][0]


def _metrics_of(entries: list[dict], cell_name: str) -> tuple[dict, ...]:
    return tuple(
        m for m in entries if "workloads" not in m or cell_name in m["workloads"]
    )


def load_cell(name: str, root: Path = ROOT) -> Cell:
    benchmark = load_benchmark(root)
    entries = [w for w in benchmark["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in benchmark["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (have: {known})")
    (entry,) = entries
    configs = {c["name"]: c for c in benchmark["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(f"workload {name!r} names unknown config {entry['config']!r}")
    config_file = root / configs[entry["config"]]["file"]
    traffic_file = _bench_dir(benchmark, root) / "traffic" / f"{entry['traffic']}.json"
    for path in (config_file, traffic_file):
        if not path.is_file():
            raise ManifestError(f"workload {name!r}: missing {path.relative_to(root)}")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=json.loads(config_file.read_text()),
        config_dir=config_file.parent,
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=_metrics_of(benchmark["end_to_end"], name),
        per_layer=_metrics_of(benchmark["per_layer"], name),
    )


def layer_metric_spec(metric_name: str, root: Path = ROOT) -> tuple[dict, Path]:
    """The reader definition of one per-layer metric, and its directory."""
    directory = _bench_dir(load_benchmark(root), root) / "layer_metrics"
    path = directory / f"{metric_name}.json"
    if not path.is_file():
        raise ManifestError(f"per-layer metric {metric_name!r}: missing {path.relative_to(root)}")
    return json.loads(path.read_text()), directory


def validate(root: Path = ROOT) -> list[str]:
    """Every breach of the naming rules and every file a name fails to find."""
    benchmark = load_benchmark(root)
    problems: list[str] = []

    def name_ok(value: str, what: str) -> None:
        if not NAME_RE.match(value):
            problems.append(f"{what}: bad name {value!r}")

    def unique(values: list[str], what: str) -> None:
        if len(set(values)) != len(values):
            problems.append(f"{what}: names repeat")

    metrics = benchmark["end_to_end"] + benchmark["per_layer"]
    unique([m["name"] for m in metrics], "metrics")
    unique([w["name"] for w in benchmark["workloads"]], "workloads")
    unique([c["name"] for c in benchmark["configs"]], "configs")
    unique([f"{w['config']} {w['traffic']}" for w in benchmark["workloads"]], "config/traffic pairs")
    end_to_end_names = {m["name"] for m in benchmark["end_to_end"]}
    if "setup_s" not in end_to_end_names:
        problems.append("end_to_end: no setup_s")
    for metric in metrics:
        name_ok(metric["name"], "metric")
        if not UNIT_RE.match(metric["unit"]):
            problems.append(f"metric {metric['name']}: bad unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            problems.append(f"metric {metric['name']}: better is {metric['better']!r}")
        if metric["source"] not in SOURCES:
            problems.append(f"metric {metric['name']}: source is {metric['source']!r}")
    for metric in benchmark["end_to_end"]:
        if metric["source"] not in ("host_clock", "device_trace"):
            problems.append(f"end-to-end metric {metric['name']}: source {metric['source']!r}")
        if not 0.01 <= metric["bound"] <= 0.25:
            problems.append(f"end-to-end metric {metric['name']}: bound {metric['bound']}")
    for metric in benchmark["per_layer"]:
        if metric["moves"] not in end_to_end_names:
            problems.append(f"per-layer metric {metric['name']}: moves {metric['moves']!r}")
        try:
            spec, directory = layer_metric_spec(metric["name"], root)
        except ManifestError as error:
            problems.append(str(error))
            continue
        if spec.get("reader") == "module" and not (directory / f"{metric['name']}.py").is_file():
            problems.append(f"per-layer metric {metric['name']}: no reader module beside its file")
    for config in benchmark["configs"]:
        name_ok(config["name"], "config")
        for key in config["reduced"]:
            name_ok(key, f"config {config['name']} reduced")
        if not any(config["file"].startswith(p + "/") for p in benchmark["paths"]):
            problems.append(f"config {config['name']}: file outside paths")
    used_configs = set()
    four_chip = 0
    for workload in benchmark["workloads"]:
        name_ok(workload["name"], "workload")
        name_ok(workload["traffic"], "traffic")
        if workload["chips"] not in (1, 4):
            problems.append(f"workload {workload['name']}: chips {workload['chips']}")
        four_chip += workload["chips"] == 4
        if len(workload["why"]) > 200 or "\n" in workload["why"] or "\t" in workload["why"]:
            problems.append(f"workload {workload['name']}: why is not one line of <= 200")
        used_configs.add(workload["config"])
        try:
            cell = load_cell(workload["name"], root)
        except ManifestError as error:
            problems.append(str(error))
            continue
        if len(cell.end_to_end) < 2 or not cell.per_layer:
            problems.append(f"workload {workload['name']}: too few metrics")
    if four_chip > max(1, len(benchmark["workloads"]) // 2):
        problems.append("more than half of the cells ask for 4 chips")
    for config in benchmark["configs"]:
        if config["name"] not in used_configs:
            problems.append(f"config {config['name']}: used by no cell")
    return problems


def listing(root: Path = ROOT) -> str:
    """Every metric with its unit and layer, every cell with its
    configuration and chips: what `run.py --list` prints."""
    benchmark = load_benchmark(root)
    lines = ["metrics:"]
    for metric in benchmark["end_to_end"]:
        lines.append(
            f"  {metric['name']:<24} {metric['unit']:<10} end-to-end   "
            f"{metric['better']} is better, bound {metric['bound']:.3f}, {metric['source']}"
        )
    for metric in benchmark["per_layer"]:
        lines.append(
            f"  {metric['name']:<24} {metric['unit']:<10} layer: {metric['layer']}   "
            f"moves {metric['moves']}, {metric['source']}"
        )
    lines.append("cells:")
    for workload in benchmark["workloads"]:
        lines.append(
            f"  {workload['name']:<22} config {workload['config']:<20} "
            f"traffic {workload['traffic']:<20} chips {workload['chips']}"
        )
    return "\n".join(lines)
