"""Files a second from 2 s after the kill to the window's end: the
completion times of every file from the kill on
(`run["kill"]["files_after"]`) that fall in that span, over its length."""

SETTLE_SECONDS = 2.0


def read(run: dict) -> float | None:
    kill = run.get("kill")
    if kill is None or kill.get("files_after") is None:
        return None
    first, last = kill["at"] + SETTLE_SECONDS, kill["window_end"]
    if last <= first:
        return None
    return sum(1 for at in kill["files_after"] if first < at <= last) / (last - first)
