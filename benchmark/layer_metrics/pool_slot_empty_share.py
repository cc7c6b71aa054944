"""Share of the pool's render-loop time spent with no queued frame: the
workers' increase of `worker_loop_seconds_total{state="no_work"}` over
workers x window, in percent."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    waited = scrape.delta(before, after, "worker_loop_seconds_total", {"state": "no_work"})
    if waited is None or not run["workers"] or not run["window_s"]:
        return None
    return 100.0 * waited / (run["workers"] * run["window_s"])
