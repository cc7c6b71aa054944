"""Steps of the resident top over all steps of the streamed walk: the
window's increase of `render_walk_node_visits_total` less those of
`render_walk_leaf_tests_total`, `render_walk_treelet_entries_total` and
`render_walk_group_tests_total` (the steps inside treelets), over the
visits. Nothing to read from a program without any of the four."""

from benchmark.lib import scrape

INSIDE = ("render_walk_leaf_tests_total", "render_walk_treelet_entries_total", "render_walk_group_tests_total")


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    visits = scrape.delta(before, after, "render_walk_node_visits_total")
    inside = [scrape.delta(before, after, series) for series in INSIDE]
    if not visits or None in inside:
        return None
    return 100.0 * (1.0 - sum(inside) / visits)
