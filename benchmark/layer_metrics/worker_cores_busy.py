"""The workers' CPU seconds inside the window over its length: the
increase of `worker_process_cpu_seconds_total` (both modes), summed over
workers. Cores, not a share: `worker_host_cpu_units` on the same scrape
says how many one worker's process may run on. Nothing to read from a
worker without the counter."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    used = scrape.delta(before, after, "worker_process_cpu_seconds_total")
    return None if used is None or not run["window_s"] else used / run["window_s"]
