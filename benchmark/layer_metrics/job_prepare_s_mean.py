"""Mean seconds a worker took from a job's announcement to reporting it
resident: `worker_job_prepare_seconds` summed over families, sum over
count, as the window ended (a size, not an increase: the preparations
that cost anything lie before the window). Nothing to read from a program
without the histogram."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    _, after = run["scrapes"]["workers"]
    total = [scrape.total(one, "worker_job_prepare_seconds_sum") for one in after]
    count = [scrape.total(one, "worker_job_prepare_seconds_count") for one in after]
    if any(value is None for value in total + count) or not sum(count):
        return None
    return sum(total) / sum(count)
