"""Jobs the service reported finished per minute of the window, over the
pool: `jobs_per_min`'s reader, which lists the one-worker cell alone."""

from benchmark.lib import readers


def read(run: dict) -> float | None:
    return readers.read_metric("jobs_per_min", run)
