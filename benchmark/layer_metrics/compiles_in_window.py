"""Programs compiled inside the window; must be 0. The larger of the
increase of `render_compiles_total` (the wavefront and raypool drivers'
own count) and the number of new entries in the persistent compile cache
(every program is cached, so this sees the other tiers too)."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    before, after = run["scrapes"]["workers"]
    counted = scrape.delta(before, after, "render_compiles_total") or 0.0
    return float(max(counted, run["cache_entries_delta"]))
