"""Seconds JAX spent in `trace` + `lower` while the worker built its programs:
the counter `render_jax_compile_seconds_total` as the window ended (no
program is built in the window, so the total is start-up's); the worker's
that spent most where there are several. Nothing to read from a program
without the counter."""

from benchmark.lib import startup_metrics


def read(run: dict) -> float | None:
    return startup_metrics.compile_seconds(run, "trace", "lower")
