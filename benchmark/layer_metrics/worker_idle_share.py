"""Share of its time in which a worker's render loop held no frame:
1 - (seconds a frame held the loop: mean read + render + write phase over
the window, from the workers' phase histogram) x frames_per_s / workers.

Not 1 - phase seconds / window: the histogram is fed when a frame ends,
and under the raypool frames end in bursts, so the phase seconds that fall
inside a window swing by a whole burst of ten seconds' work."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    held = [
        scrape.delta(before, after, "worker_frame_phase_seconds_sum", {"phase": phase})
        for phase in ("read", "render", "write")
    ]
    frames = scrape.delta(before, after, "worker_frame_phase_seconds_count", {"phase": "render"})
    if any(value is None for value in held) or not frames:
        return None
    return 100.0 * (1.0 - sum(held) / frames * run["frames_per_s"] / run["workers"])
