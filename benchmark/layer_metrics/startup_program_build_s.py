"""Seconds of a worker's start-up in `program_build`: the gauge
`worker_startup_stage_seconds` as the window ended (a size, not an
increase); the slowest worker's where there are several. Nothing
to read from a program without the gauge."""

from benchmark.lib import startup_metrics


def read(run: dict) -> float | None:
    return startup_metrics.stage_seconds(run, "program_build")
