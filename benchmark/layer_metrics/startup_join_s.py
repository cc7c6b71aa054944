"""Seconds of a worker's start-up in `connect` + `await_job`: the gauge
`worker_startup_stage_seconds` as the window ended (a size, not an
increase); the smallest over the workers: the last to connect waits for
nobody, so its is the transport's and the master's own time (the others'
wait for the slowest shows in the timeline). Nothing to read from a
program without the gauge."""

from benchmark.lib import startup_metrics


def read(run: dict) -> float | None:
    return startup_metrics.stage_seconds(run, "connect", "await_job", pick=min)
