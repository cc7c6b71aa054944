"""BLASes the scene holds: the workers' gauge `render_geometry_blas_units` as the
window ended (a size, not an increase). Nothing to read from a program
without the gauge."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    _, after = run["scrapes"]["workers"]
    values = [scrape.total(one, "render_geometry_blas_units") for one in after]
    values = [value for value in values if value is not None]
    return max(values) if values else None
