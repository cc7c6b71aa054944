"""Image output: frame-placeholder expansion + PNG/JPEG writing.

The ``#####`` placeholder convention matches the reference's render script
(reference: scripts/render-timing-script.py:69-79): the run of ``#`` is
replaced by the zero-padded frame number.

Which format is written how (``write_image``; every one from the same
[H, W, 3] uint8 pixels the frame program's tone map produced, 8 bits a
channel, RGB, nothing between device and disk quantised again but by a
lossy format's own encoder):

- ``PNG``: Pillow's encoder at its default compression, zlib level 6, no
  ``optimize``: lossless, the file decodes to the pixels bit for bit. What
  the source's demo jobs and every tile intermediate write. Several
  times the bytes and the host time of the same frame's JPEG (PERF.md §5,
  `04vs-1w-png` beside `04vs-1w-coarse`);
- ``JPEG`` (``JPG`` is the same): quality 90, as the reference's render
  script sets it; Pillow's default 4:2:0 chroma subsampling;
- ``BMP``, ``TIFF``: Pillow's defaults (uncompressed);
- anything else: **written as PNG under a ``.png`` name**, silently. The
  fall-back is a PNG file in every respect, and is counted as one.

``tests/test_steps.py::test_write_image_writes_the_parents_bytes`` holds the
first two byte for byte.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import NamedTuple

import numpy as np

_HASH_RUN = re.compile(r"#+")

_FORMAT_EXTENSIONS = {
    "PNG": ".png",
    "JPEG": ".jpg",
    "JPG": ".jpg",
    "BMP": ".bmp",
    "TIFF": ".tif",
}


def format_frame_placeholders(name_format: str, frame_number: int) -> str:
    """Replace the run of '#' with the zero-padded frame number."""
    match = _HASH_RUN.search(name_format)
    if match is None:
        return f"{name_format}{frame_number}"
    width = match.end() - match.start()
    return (
        name_format[: match.start()]
        + str(frame_number).rjust(width, "0")
        + name_format[match.end():]
    )


def output_path_for_frame(
    output_directory: Path, name_format: str, file_format: str, frame_number: int
) -> Path:
    extension = _FORMAT_EXTENSIONS.get(file_format.upper(), ".png")
    return output_directory / (
        format_frame_placeholders(name_format, frame_number) + extension
    )


def output_path_for_tile(
    output_directory: Path,
    name_format: str,
    file_format: str,
    frame_number: int,
    tile: int,
    grid: tuple[int, int],
) -> Path:
    """Where one tile of a tiled frame lands: the frame's own output path
    with a ``.tile_rRcC`` infix — always ``.png``. Tile intermediates are
    LOSSLESS regardless of the job's final format: encoding each tile of
    a JPEG job lossily and re-encoding the stitched frame would quantize
    twice (with independent per-tile block boundaries) and break the
    tiled-equals-untiled pixel contract. Workers (writing) and the
    master's assembler (reading/stitching) both resolve through here, so
    the naming cannot drift."""
    from tpu_render_cluster.jobs.tiles import tile_rc

    frame_path = output_path_for_frame(
        output_directory, name_format, file_format, frame_number
    )
    row, col = tile_rc(tile, grid)
    return frame_path.with_name(
        f"{frame_path.stem}.tile_r{row}c{col}.png"
    )


def written_format(file_format: str) -> str:
    """The format ``write_image`` writes for a job's ``file_format``: its
    upper case, ``JPG`` as ``JPEG``, and ``PNG`` for one it does not know."""
    image_format = file_format.upper()
    if image_format == "JPG":
        image_format = "JPEG"
    return image_format if image_format in _FORMAT_EXTENSIONS else "PNG"


# The formats ``written_format`` can answer: the label values of
# ``worker_frame_file_bytes_total{format}``.
WRITTEN_FORMATS = tuple(sorted({written_format(name) for name in _FORMAT_EXTENSIONS}))


class WrittenImage(NamedTuple):
    """What one ``write_image`` call turned into what."""

    image_format: str  # of WRITTEN_FORMATS
    pixel_bytes: int  # the raw u8 bytes handed to the encoder
    file_bytes: int  # the encoder's bytes, all of them renamed into place
    # seconds of each of obs.FILE_WRITE_OPS (mkdir, create, write, close,
    # rename), edge to edge from the ``file_write`` step's start to the
    # rename's return
    write_op_seconds: tuple[float, float, float, float, float]


def write_image(path: Path, pixels: np.ndarray, file_format: str = "PNG") -> WrittenImage:
    """Write a [H, W, 3] uint8 array in ``written_format(file_format)``:
    PNG at Pillow's default compression (zlib level 6), JPEG at quality
    90, and PNG for a format nobody here knows (the module's docstring).

    Two frame steps (obs.step): ``encode`` turns the pixels into the
    format's bytes in memory, ``file_write`` puts them on disk. Returns
    the bytes that went into ``encode`` and came out of it, and what each
    of ``obs.FILE_WRITE_OPS`` took of the ``file_write`` step: the worker's
    queue counts them and writes them on the two steps' events.

    Atomic (write-temp-then-rename): a reader never sees a torn file.
    Load-bearing for tile assembly — a duplicate assignment of the same
    tile (queue-add ack timeout races) can still be writing the tile path
    when the master's stitcher reads it; both copies carry identical
    pixels, so with the rename either complete version is correct.
    """
    import io
    import os
    import tempfile
    import time

    from PIL import Image

    from tpu_render_cluster.obs import step

    image_format = written_format(file_format)
    with step("encode"):
        pixels = np.asarray(pixels)
        image = Image.fromarray(pixels)
        encoded = io.BytesIO()
        if image_format == "JPEG":
            # reference script: quality=90
            image.save(encoded, image_format, quality=90)
        else:
            image.save(encoded, image_format)
    with step("file_write"):
        edges = [time.perf_counter()]
        path.parent.mkdir(parents=True, exist_ok=True)
        edges.append(time.perf_counter())
        fd, tmp_name = tempfile.mkstemp(
            prefix=f".{path.name}.", suffix=".tmp", dir=path.parent
        )
        edges.append(time.perf_counter())
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(encoded.getbuffer())
                edges.append(time.perf_counter())
            edges.append(time.perf_counter())
            os.replace(tmp_name, path)
            edges.append(time.perf_counter())
        except BaseException:
            try:
                os.unlink(tmp_name)
            except OSError:
                pass
            raise
    return WrittenImage(
        image_format,
        pixels.nbytes,
        encoded.getbuffer().nbytes,
        tuple(end - start for start, end in zip(edges, edges[1:])),
    )
