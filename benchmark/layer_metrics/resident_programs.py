"""Frame programs resident in a worker, one a (scene family, shape): the
gauge `render_resident_program_units` as the window ended; the largest over
the workers. Nothing to read from a program without the gauge."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    _, after = run["scrapes"]["workers"]
    values = [scrape.total(one, "render_resident_program_units") for one in after]
    values = [value for value in values if value is not None]
    return max(values) if values else None
