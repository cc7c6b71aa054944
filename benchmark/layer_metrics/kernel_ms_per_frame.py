"""Device milliseconds of the Pallas kernels per frame, from the profiler
trace of the slice (summed over chips)."""

from benchmark.lib.readers import slice_seconds_per_frame


def read(run: dict) -> float | None:
    seconds = slice_seconds_per_frame(run, "kernel_s")
    return None if seconds is None else 1000.0 * seconds
