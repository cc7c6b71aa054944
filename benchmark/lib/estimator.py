"""Frames per second from the completion times of a window.

`slope_rate` is the least-squares slope of completion index over
completion time: the mean rate over everything the window saw, stalls
included. It is what ISSUE 23 asked for, and it is used in every cell.

What it needs from the window: the raypool lands 8 frames within a tenth
of a second and then nothing for ten seconds, and a fit over a window
that begins inside such a burst reads 4% low (PERF.md, PR 23). The driver
therefore begins the window in a lull (`drivers/backlog.py`); a window of
whole bursts gives the bursts' rate whatever its length. Counting between
the window's edges swings by a whole burst, and first-to-last is always
one burst short of its span.

What it costs: about once in a hundred seconds the one-worker sphere cell
stops for 1.5 s. A window either holds such a stall or does not, the slope
follows it (3-5%), and so the runs of that cell spread by as much. That
is the served path's own behaviour and is left in the number on purpose:
a PR that removes the stalls has to show, and one that adds them too.
"""

from __future__ import annotations

import numpy as np


def slope_rate(times: list[float]) -> float | None:
    """Least-squares slope of completion index over completion time, in
    completions per second; None where the window holds too few."""
    t = np.sort(np.asarray(times, dtype=np.float64))
    if len(t) < 3 or t[-1] <= t[0]:
        return None
    return float(np.polyfit(t - t.mean(), np.arange(len(t), dtype=np.float64), 1)[0])


def per_second(times: list[float], start: float, seconds: float) -> list[int]:
    """Completions in each whole second of the window: short enough for a
    line of detail, and enough to see a burst or a stall."""
    edges = np.arange(0.0, np.ceil(seconds) + 1.0)
    return np.histogram(np.asarray(times, dtype=np.float64) - start, bins=edges)[0].tolist()
