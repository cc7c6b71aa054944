"""The directory's part of the `file_write` step, per frame, in ms: the
increase of `worker_file_write_op_seconds_total{op}` for `mkdir`, `create`
and `rename`, summed, over the window's frames. Nothing to read from a
worker without the counter."""

from benchmark.lib import scrape

DIRECTORY_OPS = ("mkdir", "create", "rename")


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    seconds = [
        scrape.delta(before, after, "worker_file_write_op_seconds_total", {"op": op})
        for op in DIRECTORY_OPS
    ]
    frames = scrape.delta(before, after, "worker_frame_phase_seconds_count", {"phase": "render"})
    if any(value is None for value in seconds) or not frames:
        return None
    return 1000.0 * sum(seconds) / frames
