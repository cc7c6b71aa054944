"""Per-layer metrics: one small reader each, found by the metric's name.

`layer_metrics/<name>.json` says how the metric is read:

- `{"reader": "delta_ratio", "from": "workers"|"master", "numerator": {...},
  "denominator": {...}, "scale": k}`: the increase of one series over the
  window divided by the increase of another (a histogram's `_sum` over its
  `_count` is the mean over the window), summed over the processes scraped;
- `{"reader": "delta", "from": ..., "series": {...}}`: the increase alone;
- `{"reader": "module"}`: `layer_metrics/<name>.py` beside it defines
  `read(run) -> float | None`.

A reader that finds nothing to read returns None, and the harness leaves
that metric out of the line. `run` is what one run observed; its keys are
listed in `drivers/backlog.py` where it is built.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

from benchmark.lib import scrape
from benchmark.lib.manifest import ROOT, layer_metric_spec


def _delta(run: dict, source: str, series: dict) -> float | None:
    before, after = run["scrapes"][source]
    return scrape.delta(before, after, series["series"], series.get("labels"))


def slice_seconds_per_frame(run: dict, key: str) -> float | None:
    """Device seconds per frame from the traced slice: `key` (`busy_s` or
    `kernel_s`) summed over chips, over the frames the slice stands for.
    That is slice seconds x the run's own frames_per_s, not the handful of
    files that happened to land inside the slice."""
    trace = run["trace"]
    if not trace or not trace["devices"] or not run["frames_per_s"]:
        return None
    devices = trace["devices"]
    slice_s = sum(device["slice_s"] for device in devices) / len(devices)
    return sum(device[key] for device in devices) / (slice_s * run["frames_per_s"])


def read_metric(name: str, run: dict, root: Path = ROOT) -> float | None:
    spec, directory = layer_metric_spec(name, root)
    reader = spec["reader"]
    if reader == "delta":
        value = _delta(run, spec["from"], spec["series"])
        return None if value is None else value * spec.get("scale", 1.0)
    if reader == "delta_ratio":
        numerator = _delta(run, spec["from"], spec["numerator"])
        denominator = _delta(run, spec["from"], spec["denominator"])
        if numerator is None or not denominator:
            return None
        return numerator / denominator * spec.get("scale", 1.0)
    if reader == "module":
        module_spec = importlib.util.spec_from_file_location(
            f"benchmark_layer_metric_{name}", directory / f"{name}.py"
        )
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return module.read(run)
    raise ValueError(f"per-layer metric {name!r}: unknown reader {reader!r}")
