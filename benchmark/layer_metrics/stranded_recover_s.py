"""Seconds from the kill to the whole file of the last unit that was
stranded on the dead worker (`run["kill"]["stranded"]`: job, frame, when
it came back, its file's mtime): the time to recover that a user feels.
0 where nothing was stranded; nothing to read while a stranded unit has no
file."""


def read(run: dict) -> float | None:
    kill = run.get("kill")
    if kill is None or kill.get("stranded") is None:
        return None
    files = [file_at for _job, _frame, _back_at, file_at in kill["stranded"]]
    if any(file_at is None for file_at in files):
        return None
    return max((file_at - kill["at"] for file_at in files), default=0.0)
