"""Mean size of the frame files completed inside the window."""


def read(run: dict) -> float | None:
    sizes = [size for _, _, size in run["files"]]
    return sum(sizes) / len(sizes) if sizes else None
