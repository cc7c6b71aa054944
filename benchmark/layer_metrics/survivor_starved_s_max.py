"""The largest rise of `worker_loop_seconds_total{state="no_work"}` over
any survivor from the kill to the window's end: each survivor's scrape at
the kill against its scrape as the window ended
(`run["kill"]["survivor_scrapes"]`)."""

from benchmark.lib import scrape


def read(run: dict) -> float | None:
    kill = run.get("kill")
    if kill is None or not kill.get("survivor_scrapes"):
        return None
    at_kill, at_end = kill["survivor_scrapes"]
    rises = [
        scrape.delta([first], [last], "worker_loop_seconds_total", {"state": "no_work"})
        for first, last in zip(at_kill, at_end)
    ]
    rises = [rise for rise in rises if rise is not None]
    return max(rises) if rises else None
