"""What the ten start-up metrics read: the workers' own account of their
start-up, scraped as the window ends (sizes, not increases).

`worker_startup_stage_seconds{stage}` holds the eight exclusive stages of a
worker's start-up, from the kernel's start of its process to its first
frame file in place (`tpu_render_cluster/obs/startup.py`); the
`render_jax_compile_*` and `render_compile_cache_*` counters hold JAX's own
account of the programs it built. No program is built inside the window,
so a counter's total as the window ends is start-up's. A worker whose
scrape lacks a series (a program from before them) gives nothing, and a
reader with no worker to read returns None: the harness then leaves the
metric off the line.
"""

from __future__ import annotations

from typing import Callable, Iterable

from benchmark.lib import scrape


def _per_worker(run: dict, series: str, label: str, values: Iterable[str]) -> list[float]:
    """Per worker, the sum of `series` over `label` in `values`; a worker
    that lacks one of them is left out."""
    _, after = run["scrapes"]["workers"]
    sums = []
    for one in after:
        parts = [scrape.total(one, series, {label: value}) for value in values]
        if all(part is not None for part in parts):
            sums.append(sum(parts))
    return sums


def stage_seconds(run: dict, *stages: str, pick: Callable = max) -> float | None:
    """Seconds of the named stages together, of the worker `pick` chooses:
    the slowest sets the pace (the master starts the job when the last
    worker has connected)."""
    sums = _per_worker(run, "worker_startup_stage_seconds", "stage", stages)
    return pick(sums) if sums else None


def compile_seconds(run: dict, *phases: str) -> float | None:
    """Seconds JAX spent in the named phases of building programs, of the
    worker that spent most."""
    sums = _per_worker(run, "render_jax_compile_seconds_total", "phase", phases)
    return max(sums) if sums else None


def cache_hit_share(run: dict) -> float | None:
    """100 x hits / (hits + misses) of the persistent compilation cache,
    over all workers; 0 where nothing was asked of it."""
    series = "render_compile_cache_requests_total"
    hits = _per_worker(run, series, "result", ("hit",))
    asked = _per_worker(run, series, "result", ("hit", "miss"))
    if not asked:
        return None
    return 100.0 * sum(hits) / sum(asked) if sum(asked) else 0.0
