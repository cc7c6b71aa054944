"""Units the master reports as having left the dead worker at its
eviction: the `handbacks` reports that name the killed worker with the
cause `eviction`, as `plain_failover.account` lists them
(`run["kill"]["stranded"]`)."""


def read(run: dict) -> float | None:
    kill = run.get("kill")
    return None if kill is None or kill.get("stranded") is None else float(len(kill["stranded"]))
