"""Image output: frame-placeholder expansion + PNG/JPEG writing.

The ``#####`` placeholder convention matches the reference's render script
(reference: scripts/render-timing-script.py:69-79): the run of ``#`` is
replaced by the zero-padded frame number.
"""

from __future__ import annotations

import re
from pathlib import Path

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


def write_image(path: Path, pixels: np.ndarray, file_format: str = "PNG") -> None:
    """Write a [H, W, 3] uint8 array; falls back to PNG for unknown formats.

    Two frame steps (obs.step): ``encode`` turns the pixels into the
    format's bytes in memory, ``file_write`` puts them on disk.

    Atomic (write-temp-then-rename): a reader never sees a torn file.
    Load-bearing for tile assembly — a duplicate assignment of the same
    tile (queue-add ack timeout races) can still be writing the tile path
    when the master's stitcher reads it; both copies carry identical
    pixels, so with the rename either complete version is correct.
    """
    import io
    import os
    import tempfile

    from PIL import Image

    from tpu_render_cluster.obs import step

    image_format = file_format.upper()
    if image_format == "JPG":
        image_format = "JPEG"
    if image_format not in _FORMAT_EXTENSIONS:
        image_format = "PNG"
    with step("encode"):
        image = Image.fromarray(np.asarray(pixels))
        encoded = io.BytesIO()
        if image_format == "JPEG":
            # reference script: quality=90
            image.save(encoded, image_format, quality=90)
        else:
            image.save(encoded, image_format)
    with step("file_write"):
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp_name = tempfile.mkstemp(
            prefix=f".{path.name}.", suffix=".tmp", dir=path.parent
        )
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(encoded.getbuffer())
            os.replace(tmp_name, path)
        except BaseException:
            try:
                os.unlink(tmp_name)
            except OSError:
                pass
            raise
