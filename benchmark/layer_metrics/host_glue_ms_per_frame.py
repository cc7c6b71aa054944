"""Host milliseconds of the render phase per frame in which the device did
nothing for it: the render phase's mean (workers' histogram, the window)
minus the device's busy time per frame (trace, the slice). Dispatch, sync
and readback."""

from benchmark.lib import scrape
from benchmark.lib.readers import slice_seconds_per_frame


def read(run: dict) -> float | None:
    busy = slice_seconds_per_frame(run, "busy_s")
    before, after = run["scrapes"]["workers"]
    total = scrape.delta(before, after, "worker_frame_phase_seconds_sum", {"phase": "render"})
    count = scrape.delta(before, after, "worker_frame_phase_seconds_count", {"phase": "render"})
    if busy is None or total is None or not count:
        return None
    return 1000.0 * (total / count - busy)
