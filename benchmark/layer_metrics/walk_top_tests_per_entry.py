"""Steps of the resident top for each treelet the streamed walk enters: the
window's increase of `render_walk_node_visits_total` less those of
`render_walk_leaf_tests_total`, `render_walk_group_tests_total` and
`render_walk_treelet_entries_total` (the steps inside treelets), over the
entries. Nothing to read from a program without any of the four, or from a
window in which no treelet was entered."""

from benchmark.lib import scrape

ENTRIES = "render_walk_treelet_entries_total"
INSIDE = ("render_walk_leaf_tests_total", "render_walk_group_tests_total", ENTRIES)


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    visits = scrape.delta(before, after, "render_walk_node_visits_total")
    inside = {series: scrape.delta(before, after, series) for series in INSIDE}
    if visits is None or None in inside.values() or not inside[ENTRIES]:
        return None
    return (visits - sum(inside.values())) / inside[ENTRIES]
