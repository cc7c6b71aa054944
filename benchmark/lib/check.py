"""The comparison that decides `correct`.

Three parts, all outside the measured window:

- every file counted decodes to the render shape and is not flat; frame
  numbers are distinct and inside the job's range;
- same-stream check: seeded frames and a seeded crop, rendered again by a
  CPU-pinned child running the program's own kernels in the Pallas
  interpreter (same random streams, float32 on the CPU), both sides through
  JPEG, compared pixel by pixel on the crop's interior;
- independent check (configurations that name a reference): block means of
  a served crop against `reference/plain_tracer.py`, a NumPy tracer with
  its own random numbers, within the spread of the reference's own
  replicas.

What is checked follows from the seed and the job's first frame alone,
not from which files a window happened to hold: the frames are the first
multiples of a quantum once the warm-up's frames are past (inside the
window in every cell, in its first third), and the crop is one of the few
the configuration lists. Each listed crop holds geometry: on
sky or far floor a wrong contraction changes nothing and the check would
pass whatever the kernels did (PERF.md, PR 23). So the references of a
cell are few, and after a checkout's first runs they come from the cache
under `benchmark/.cache/`, keyed by configuration, shape, the program's
render code, frame and crop.
"""

from __future__ import annotations

import hashlib
import importlib
import io
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
from PIL import Image

from benchmark.lib.manifest import BENCH_DIR, ROOT, Cell

CACHE_DIR = BENCH_DIR / ".cache"
FLAT_STD = 2.0  # a frame whose pixels spread by less than this is an empty image
CHILD_SECONDS = 240


def mix(value: int) -> int:
    """A fixed 64-bit integer mix (splitmix64's finaliser): what turns
    `--seed` into frame numbers and crops. Not Python's `hash`, which is
    salted per process."""
    value = (value + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    value = ((value ^ (value >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    value = ((value ^ (value >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return value ^ (value >> 31)


def frame_number(path: Path) -> int | None:
    match = re.search(r"(\d+)$", path.stem)
    return int(match.group(1)) if match else None


def load_rgb(path: Path) -> np.ndarray | None:
    try:
        with Image.open(path) as image:
            return np.asarray(image.convert("RGB"))
    except (OSError, ValueError):
        return None


def check_files(
    paths: list[Path], *, width: int, height: int, first_frame: int,
    last_frame: int, decode_at_most: int = 256,
) -> tuple[int, list[str]]:
    """(number of bad files, what was wrong) over the window's files.

    Every file's header is read (format and shape) and its frame number
    checked; an even sample of at most `decode_at_most` files is decoded in
    full and must not be flat. Decoding every file of a window of
    thousands would cost more chip time than the window."""
    problems: list[str] = []
    seen: set[int] = set()
    stride = max(1, -(-len(paths) // decode_at_most))
    for index, path in enumerate(paths):
        number = frame_number(path)
        reason = None
        if number is None or not first_frame <= number <= last_frame:
            reason = f"frame number outside {first_frame}..{last_frame}"
        elif number in seen:
            reason = "frame counted twice"
        else:
            try:
                with Image.open(path) as image:
                    if image.size != (width, height):
                        reason = f"shape {image.size}"
                    elif index % stride == 0:
                        pixels = np.asarray(image.convert("RGB"))
                        if pixels.std() < FLAT_STD:
                            reason = f"flat image (std {pixels.std():.2f})"
            except (OSError, ValueError):
                reason = "missing or undecodable"
        if number is not None:
            seen.add(number)
        if reason:
            problems.append(f"{path.name}: {reason}")
    return len(problems), problems


def jpeg_round_trip(pixels: np.ndarray, quality: int) -> np.ndarray:
    buffer = io.BytesIO()
    Image.fromarray(pixels).save(buffer, "JPEG", quality=quality)
    buffer.seek(0)
    with Image.open(buffer) as image:
        return np.asarray(image.convert("RGB"))


def checked_frames(first_frame: int, last_frame: int, spec: dict) -> list[int]:
    """The frames the image checks look at: `count` frames `step` apart
    from the first multiple of `quantum` that lies `after` frames or more
    past the job's first frame (`spec` is the configuration's
    `check.frames`; `after` covers the warm-up, so the frames lie inside
    the window). For a configuration whose seed moves the first frame by
    less than the quantum they take two values at most, so their
    references are cached."""
    anchor = -(-(first_frame + spec["after"]) // spec["quantum"]) * spec["quantum"]
    return [f for f in (anchor + k * spec["step"] for k in range(spec["count"])) if f <= last_frame]


def pick_crop(crops: list[list[int]], seed: int, salt: int, *, width: int, height: int, crop: int) -> tuple[int, int]:
    """(y0, x0): one of the configuration's listed crops, by the seed;
    moved inside the frame where a rehearsal renders a smaller one."""
    y0, x0 = crops[mix(seed * 4 + salt) % len(crops)]
    return min(y0, height - crop), min(x0, width - crop)


def code_stamp() -> str:
    """A short hash of the program's render code: a reference rendered by
    other code is not found in the cache."""
    digest = hashlib.sha1()
    for path in sorted((ROOT / "tpu_render_cluster" / "render").glob("*.py")):
        digest.update(path.read_bytes())
    return digest.hexdigest()[:10]


def _run_region_child(request: dict, out_path: Path, env: dict[str, str]) -> dict:
    """Crops (and scene arrays) from the program, run on the CPU."""
    out_path.parent.mkdir(parents=True, exist_ok=True)
    request_path = out_path.with_suffix(".request.json")
    request_path.write_text(json.dumps(request))
    child_env = {
        **env, "JAX_PLATFORMS": "cpu", "TRC_PALLAS": "1",
        "PYTHONPATH": str(ROOT),
    }
    result = subprocess.run(
        [sys.executable, str(BENCH_DIR / "lib" / "region_child.py"), str(request_path), str(out_path)],
        env=child_env, cwd=ROOT, capture_output=True, text=True, timeout=CHILD_SECONDS,
    )
    request_path.unlink(missing_ok=True)
    if result.returncode != 0:
        raise RuntimeError(f"region child failed:\n{result.stderr[-2000:]}")
    with np.load(out_path) as data:
        return {key: data[key] for key in data.files}


def same_stream_agreement(
    served: np.ndarray, reference_crop: np.ndarray, *, y0: int, x0: int,
    border: int, max_levels: int, quality: int | None,
) -> float:
    """Share of the crop's interior pixels on which the served frame and
    the reference, through the same encoder, differ by at most
    `max_levels` in every channel."""
    crop = reference_crop.shape[0]
    if quality is not None:
        reference_crop = jpeg_round_trip(reference_crop, quality)
    inner = slice(border, crop - border)
    ours = served[y0:y0 + crop, x0:x0 + crop][inner, inner].astype(np.int16)
    theirs = reference_crop[inner, inner].astype(np.int16)
    return float((np.abs(ours - theirs).max(axis=-1) <= max_levels).mean())


def block_means(pixels: np.ndarray, block: int) -> np.ndarray:
    size = pixels.shape[0] // block
    return pixels[: size * block, : size * block].astype(np.float64).reshape(
        size, block, size, block, 3
    ).mean(axis=(1, 3))


def independent_agreement(
    served_crop: np.ndarray, replicas: np.ndarray, *, block: int, sigmas: float, abs_levels: float
) -> tuple[bool, float]:
    """Whether every block mean of the served crop lies within the
    replicas' own spread; also the worst excess in u8 levels."""
    means = np.stack([block_means(replica, block) for replica in replicas])
    centre = means.mean(axis=0)
    spread = means.std(axis=0, ddof=1) * np.sqrt(1.0 + 1.0 / len(replicas))
    excess = np.abs(block_means(served_crop, block) - centre) - (sigmas * spread + abs_levels)
    return bool((excess <= 0).all()), float(excess.max())


def _save(path: Path, **arrays) -> None:
    """Into the cache under a temporary name first: a run cut while it
    writes leaves no half file for the next one to load."""
    path.parent.mkdir(parents=True, exist_ok=True)
    temporary = path.with_name(path.name + ".tmp.npz")
    np.savez(temporary, **arrays)
    os.replace(temporary, path)


def _reference_crops(
    cache: Path, job_name: str, frames: list[int], y0: int, x0: int, crop: int,
    shape: dict, env: dict[str, str],
) -> dict[int, np.ndarray]:
    """The same-stream references of `frames`, from the cache where they
    are there; the rest from one child, and into the cache."""
    paths = {frame: cache / f"same_f{frame}_y{y0}_x{x0}_c{crop}.npz" for frame in frames}
    missing = [frame for frame, path in paths.items() if not path.is_file()]
    if missing:
        rendered = _run_region_child(
            {"job_name": job_name, "frames": missing, "y0": y0, "x0": x0, "crop": crop, "render": shape},
            cache / f"same_y{y0}_x{x0}_c{crop}.new.npz", env,
        )
        (cache / f"same_y{y0}_x{x0}_c{crop}.new.npz").unlink(missing_ok=True)
        for frame in missing:
            _save(paths[frame], reference=rendered[f"frame_{frame}"])
    references = {}
    for frame, path in paths.items():
        with np.load(path) as data:
            references[frame] = data["reference"]
    return references


def check_images(
    cell: Cell, files_by_frame: dict[int, Path], job_name: str, first_frame: int,
    last_frame: int, seed: int, env: dict[str, str],
) -> tuple[list[str], dict]:
    """The same-stream and independent checks on the seed's frames and
    crop. `files_by_frame` is every frame on disk when the run stopped.
    Returns (problems, details)."""
    spec = cell.config.get("check", {})
    shape = cell.config["render"]
    width, height = shape["width"], shape["height"]
    quality = cell.config["output"].get("jpeg_quality") if cell.config["output"]["file_format"] == "JPEG" else None
    problems: list[str] = []
    details: dict = {}
    cache = CACHE_DIR / "ref" / cell.config_name / f"{width}x{height}x{shape['samples']}" / code_stamp()

    frames = checked_frames(first_frame, last_frame, spec["frames"]) if spec else []
    served = {frame: load_rgb(files_by_frame[frame]) if frame in files_by_frame else None for frame in frames}
    problems += [
        f"frame {frame} is to be checked and is not whole on disk" for frame, pixels in served.items() if pixels is None
    ]
    same = spec.get("same_stream")
    if same:
        crop = min(same["crop"], height, width)
        border = same["border"] if crop > 4 * same["border"] else 0
        y0, x0 = pick_crop(same["crops"], seed, 0, width=width, height=height, crop=crop)
        references = _reference_crops(cache, job_name, frames, y0, x0, crop, shape, env)
        agreement = {}
        for frame in frames:
            if served[frame] is None:
                continue
            share = same_stream_agreement(
                served[frame], references[frame], y0=y0, x0=x0, border=border,
                max_levels=same["max_levels"], quality=quality,
            )
            agreement[frame] = share
            if share < same["min_share"]:
                problems.append(
                    f"same-stream: frame {frame} crop ({y0},{x0}) agrees on {share:.4f} "
                    f"of pixels within {same['max_levels']} levels (want {same['min_share']})"
                )
        details["same_stream"] = {"crop": [y0, x0, crop], "agreement": agreement}

    independent = spec.get("independent")
    if independent:
        reference = importlib.import_module(f"benchmark.reference.{independent['reference']}")
        crop = min(independent["crop"], height, width)
        block = min(independent["block"], crop)
        y0, x0 = pick_crop(independent["crops"], seed, 2, width=width, height=height, crop=crop)
        frame = frames[0]
        static = independent.get("scene_is_static", False)
        which = "static" if static else f"f{frame}"
        path = cache / f"{independent['reference']}_{which}_y{y0}_x{x0}_c{crop}_r{independent['replicas']}.npz"
        if path.is_file():
            with np.load(path) as data:
                replicas = data["replicas"]
        else:
            arrays = _run_region_child(
                {"job_name": job_name, "frames": [], "y0": 0, "x0": 0, "crop": crop,
                 "render": shape, "scene_arrays_frame": frame}, path.with_suffix(".scene.npz"), env,
            )
            path.with_suffix(".scene.npz").unlink(missing_ok=True)
            replicas = reference.render_crop_replicas(
                {k[6:]: v for k, v in arrays.items() if k.startswith("scene_")},
                {k[7:]: v for k, v in arrays.items() if k.startswith("camera_")},
                {k[5:]: v for k, v in arrays.items() if k.startswith("mesh_")} or None,
                width=width, height=height, y0=y0, x0=x0, size=crop,
                samples=shape["samples"], max_bounces=shape["max_bounces"],
                replicas=independent["replicas"], seed=mix(y0 * width + x0),
            )
            _save(path, replicas=replicas)
        if served[frame] is not None:
            ok, excess = independent_agreement(
                served[frame][y0:y0 + crop, x0:x0 + crop], replicas, block=block,
                sigmas=independent["sigmas"], abs_levels=independent["abs_levels"],
            )
            details["independent"] = {"frame": frame, "crop": [y0, x0, crop], "worst_excess_levels": excess}
            if not ok:
                problems.append(
                    f"independent: frame {frame} crop ({y0},{x0}): a block mean lies {excess:.2f} "
                    "levels beyond the reference's own spread"
                )
    return problems, details
