"""Node visits of the bounce launches' packets per camera path: the
increase of `render_walk_node_visits_total` over the window's frames x
width x height x samples. Nothing to read from a program without the
counter."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    visits = scrape.delta(before, after, "render_walk_node_visits_total")
    frames = scrape.delta(before, after, "worker_frame_phase_seconds_count", {"phase": "render"})
    if visits is None or not frames:
        return None
    shape = run["render"]
    return visits / (frames * shape["width"] * shape["height"] * shape["samples"])
