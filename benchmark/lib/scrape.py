"""Read a `/metrics` endpoint (Prometheus text exposition) and take deltas.

A scrape is a dict from (series name, sorted label pairs) to value. The
program's histograms expose `<name>_sum` and `<name>_count`, which is all
the per-layer metrics need: a mean over the window is delta sum over delta
count.
"""

from __future__ import annotations

import re
import urllib.request

Scrape = dict[tuple[str, tuple[tuple[str, str], ...]], float]

_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Scrape:
    samples: Scrape = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        match = _SAMPLE.match(line)
        if match is None:
            continue
        name, labels, value = match.groups()
        pairs = tuple(sorted(_LABEL.findall(labels or "")))
        try:
            samples[(name, pairs)] = float(value)
        except ValueError:
            continue
    return samples


def fetch(port: int, timeout: float = 5.0) -> Scrape:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=timeout) as reply:
        return parse(reply.read().decode("utf-8", "replace"))


def total(scrape: Scrape, series: str, labels: dict[str, str] | None = None) -> float | None:
    """Sum of every sample of `series` whose labels include `labels`;
    None where the series is not there at all."""
    wanted = set((labels or {}).items())
    values = [
        value for (name, pairs), value in scrape.items()
        if name == series and wanted <= set(pairs)
    ]
    return sum(values) if values else None


def delta(
    before: list[Scrape], after: list[Scrape], series: str,
    labels: dict[str, str] | None = None,
) -> float | None:
    """Increase of `series` between two edges, summed over processes. A
    series that first appears after the first edge started from 0."""
    found = False
    increase = 0.0
    for first, last in zip(before, after):
        end = total(last, series, labels)
        if end is None:
            continue
        found = True
        increase += end - (total(first, series, labels) or 0.0)
    return increase if found else None
