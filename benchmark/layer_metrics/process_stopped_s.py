"""Seconds of the window in which a worker's loop was late and its own
threads were not why: the increase of `obs_loop_blocked_seconds_total`
for the role `worker`, every cause but `process_busy`, summed over
workers. 0.0 in a run without a stop; nothing to read from a worker
without the counter."""

from benchmark.lib import scrape

SERIES = "obs_loop_blocked_seconds_total"


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    late = scrape.delta(before, after, SERIES, {"role": "worker"})
    if late is None:
        return None
    crowded = scrape.delta(before, after, SERIES, {"role": "worker", "cause": "process_busy"})
    return late - (crowded or 0.0)
