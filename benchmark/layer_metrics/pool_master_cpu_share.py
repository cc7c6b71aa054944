"""The master's CPU seconds inside the window over its length, in
percent: the increase of `master_process_cpu_seconds_total`. Nothing to
read from a master without the counter."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["master"]
    used = scrape.delta(before, after, "master_process_cpu_seconds_total")
    return None if used is None or not run["window_s"] else 100.0 * used / run["window_s"]
