"""The bounce launches' HBM bytes per frame over what the chip's HBM moves
in the kernels' own time. Bytes: `lib/walk_bytes.py`, from the program's
counters (treelet bytes fetched, lanes launched) and the launch's shapes;
time: the Pallas kernels' device seconds per frame of the traced slice;
peak: `lib/peaks.py`, by the device kind the worker states in
`render_device_units`. Nothing to read where any of them is missing."""

from benchmark.lib import scrape
from benchmark.lib.peaks import chip_peaks
from benchmark.lib.readers import slice_seconds_per_frame
from benchmark.lib.walk_bytes import frame_walk_bytes


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    kinds = {
        dict(labels).get("kind") for one in after for (name, labels) in one if name == "render_device_units"
    }
    fetched = scrape.delta(before, after, "render_treelet_fetch_bytes_total")
    lanes = scrape.delta(before, after, "render_pool_launched_lanes_total")
    frames = scrape.delta(before, after, "worker_frame_phase_seconds_count", {"phase": "render"})
    kernel_s = slice_seconds_per_frame(run, "kernel_s")
    if len(kinds) != 1 or fetched is None or lanes is None or not frames or not kernel_s:
        return None
    peak = chip_peaks(kinds.pop())["hbm_bytes_per_s"]
    return 100.0 * frame_walk_bytes(fetched / frames, lanes / frames) / (kernel_s * peak)
