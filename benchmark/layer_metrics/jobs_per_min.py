"""Jobs the service reported finished per minute: the increase of the
master's `sched_jobs_finished_total` over the window, over its length.
Nothing to read from a master that is no scheduler service."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["master"]
    finished = scrape.delta(before, after, "sched_jobs_finished_total")
    return None if finished is None else 60.0 * finished / run["window_s"]
