"""What a render service owes its users on disk, with no scheduler in it.

From the list of submitted jobs and the set the service reported finished:

- every job reported finished has every frame of its range as one whole
  file of ITS shape and format in ITS directory (`must`);
- a job still in flight may have any of its range's files (`may`);
- nothing else lies under the output root: no file outside a submitted
  job's range, no file in a directory no job names.

A job here is a plain dict: `directory` (relative to the output root),
`first`, `last`, `name_format` (`#` runs are the zero-padded frame number,
as the reference's Blender script writes them), `file_format`, `width`,
`height`. `compare` walks the tree and holds it to that; it reads each
file's header (format and size), not its pixels: the image checks do that.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

from PIL import Image

EXTENSIONS = {"JPEG": ".jpg", "PNG": ".png"}

Expected = tuple[str, str, int, int, str]  # directory, file name, width, height, format


def file_name(name_format: str, frame: int, file_format: str) -> str:
    """`rendered-######` and frame 42 in JPEG: `rendered-000042.jpg`."""
    name = re.sub(r"#+", lambda run: str(frame).zfill(len(run.group())), name_format)
    return name + EXTENSIONS[file_format.upper()]


def files_of(job: dict) -> set[Expected]:
    return {
        (
            job["directory"], file_name(job["name_format"], frame, job["file_format"]),
            job["width"], job["height"], job["file_format"].upper(),
        )
        for frame in range(job["first"], job["last"] + 1)
    }


def expected(jobs: list[dict], finished: set[str]) -> tuple[set[Expected], set[Expected]]:
    """(`must`, `may`): the files that have to exist, and those that are
    allowed to. `finished` names the jobs the service reported finished."""
    must: set[Expected] = set()
    may: set[Expected] = set()
    for job in jobs:
        (must if job["name"] in finished else may).update(files_of(job))
    return must, may


def compare(root: Path, must: set[Expected], may: set[Expected]) -> list[str]:
    """Every breach: a file of `must` that is missing, a file of the wrong
    shape or format, a file that neither set allows."""
    allowed = {(directory, name): (width, height, fmt) for directory, name, width, height, fmt in must | may}
    problems: list[str] = []
    found: set[tuple[str, str]] = set()
    for current, _, names in os.walk(root):
        directory = os.path.relpath(current, root)
        for name in sorted(names):
            if name.startswith("."):
                continue  # write_image's temporary file of a frame in hand
            want = allowed.get((directory, name))
            if want is None:
                problems.append(f"{directory}/{name}: no submitted job's range holds this file")
                continue
            found.add((directory, name))
            try:
                with Image.open(Path(current) / name) as image:
                    have = (*image.size, image.format)
            except (OSError, ValueError):
                problems.append(f"{directory}/{name}: not a whole image file")
                continue
            if have != want:
                problems.append(f"{directory}/{name}: is {have}, its job states {want}")
    for directory, name, *_ in sorted(must):
        if (directory, name) not in found:
            problems.append(f"{directory}/{name}: its job was reported finished and the file is missing")
    return problems
