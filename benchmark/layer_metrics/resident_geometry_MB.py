"""Megabytes of BLAS tables a worker holds, over every family and memory
space: the gauge `render_resident_geometry_bytes{family,space}` as the
window ended (a size); the largest over the workers. Nothing to read from
a program without the gauge."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    _, after = run["scrapes"]["workers"]
    values = [scrape.total(one, "render_resident_geometry_bytes") for one in after]
    values = [value for value in values if value is not None]
    return max(values) / 1e6 if values else None
