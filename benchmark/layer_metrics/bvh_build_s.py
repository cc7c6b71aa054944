"""Seconds of `warm()` that went into the scene's BLASes: the workers'
gauge `render_bvh_build_seconds`, summed over its models, as the window
ended (a size, not an increase); the slowest worker's where there are
several. Nothing to read from a program without the gauge."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    _, after = run["scrapes"]["workers"]
    values = [scrape.total(one, "render_bvh_build_seconds") for one in after]
    values = [value for value in values if value is not None]
    return max(values) if values else None
