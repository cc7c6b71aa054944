"""(most - fewest) / mean of the workers' frames rendered inside the
window, in percent: each worker's own increase of
`worker_frames_rendered_total`."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    frames = [
        scrape.delta([first], [last], "worker_frames_rendered_total") for first, last in zip(before, after)
    ]
    if len(frames) < 2 or any(value is None for value in frames) or not sum(frames):
        return None
    return 100.0 * (max(frames) - min(frames)) / (sum(frames) / len(frames))
