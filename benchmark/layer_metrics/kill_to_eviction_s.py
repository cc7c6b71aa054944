"""Seconds from the kill to the master's declaring the worker dead: the
driver's kill instant and the `ended_at` the master's `status` gives for
the worker (`run["kill"]`). About the reconnect window where it was kept;
nothing to read where the master never said the worker was dead."""


def read(run: dict) -> float | None:
    kill = run.get("kill") or {}
    if kill.get("at") is None or kill.get("evicted_at") is None:
        return None
    return kill["evicted_at"] - kill["at"]
