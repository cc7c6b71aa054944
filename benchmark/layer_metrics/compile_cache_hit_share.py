"""Share of the executables asked of the persistent compilation cache that
it held, over all workers, as the window ended: the counter
`render_compile_cache_requests_total` by `result`. 0 where nothing was
asked; nothing to read from a program without the counter."""

from benchmark.lib import startup_metrics


def read(run: dict) -> float | None:
    return startup_metrics.cache_hit_share(run)
