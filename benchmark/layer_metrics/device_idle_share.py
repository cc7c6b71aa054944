"""Share of the traced slice in which no operation ran on the device:
1 - union of the device's operation intervals / slice, mean over chips."""


def read(run: dict) -> float | None:
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    shares = [1.0 - device["busy_s"] / device["slice_s"] for device in trace["devices"]]
    return 100.0 * sum(shares) / len(shares)
