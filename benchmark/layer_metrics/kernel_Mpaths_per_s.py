"""Camera paths per second of kernel time, in millions: width x height x
samples (the configuration's shapes) over the kernel seconds per frame
from the trace. The field's own kernel rate."""

from benchmark.lib.readers import slice_seconds_per_frame


def read(run: dict) -> float | None:
    seconds = slice_seconds_per_frame(run, "kernel_s")
    if not seconds:
        return None
    shape = run["render"]
    return shape["width"] * shape["height"] * shape["samples"] / seconds / 1e6
