"""Process start-up and device ownership: who opens the TPU, and where
compiled programs are kept.

A TPU chip belongs to ONE process at a time. The deployment shape is
therefore fixed: the master runs on the host CPU (its auction solve is at
most 128x128), and each ``tpu-raytrace`` worker process owns exactly one
chip (or, with ``--sharding``, every chip it is allowed to see). The four
helpers here are what every entry point and launcher uses to hold that
shape; nothing in this module imports JAX at import time, so launchers
that must stay off the chip (``chip_smoke.py``'s parent) can import it.
"""

from __future__ import annotations

import os
import re
import sys
from pathlib import Path

# <checkout>/.jax_cache — a fixed path per checkout (git-ignored), so a
# second process, and a second run, find what the first one compiled.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called first thing by every entry point that reaches JAX. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and the
    directory is left alone; otherwise the cache lives in
    ``DEFAULT_COMPILE_CACHE_DIR``. Every program is cached, however small
    or quick to compile: a worker compiles dozens of sub-second helper
    programs (scene build, tonemap) besides the render kernels.
    """
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return directory


def pin_jax_to_host_cpu() -> None:
    """Keep this process off the TPU (the master and its solver child).

    Must run before the first JAX backend initializes; the variable is
    set too so children inherit the pin."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")


def chip_environment(index: int) -> dict[str, str]:
    """Environment that confines a child process to local chip ``index``.

    The chip made visible, and a 1x1x1 process grid of 1x1x1 chips: each
    process is its own single-chip slice, not a rank of a shared mesh. It
    is all libtpu 0.0.34 needs — four such processes ran side by side on
    a v5e host, each holding its own ``/dev/vfio/<index>``; a port per
    process (``TPU_PROCESS_PORT``/``_ADDRESSES``) changed nothing. Merge
    over ``os.environ`` when spawning.
    """
    if index < 0:
        raise ValueError(f"chip index must be >= 0, got {index}")
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def require_tpu_device() -> dict:
    """The device this process renders on; refuses anything but a TPU.

    A ``tpu-raytrace`` worker that could not get its chip would otherwise
    come up on the CPU, render ~50x slower through the XLA twin and exit
    0. Only ``JAX_PLATFORMS`` naming ``cpu`` first (tests, CPU baselines)
    permits a non-TPU backend. The returned stamp goes into
    the worker's exported metrics snapshot.
    """
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    # "tpu,cpu" still means "the TPU or fail"; only cpu FIRST asks for it.
    first_named = (jax.config.jax_platforms or "").lower().split(",")[0]
    if platform != "tpu" and first_named != "cpu":
        raise RuntimeError(
            f"tpu-raytrace needs a TPU but JAX came up on {platform!r} "
            f"({devices[0].device_kind}); set JAX_PLATFORMS=cpu to render "
            "on the CPU on purpose."
        )
    # The chip device files this process holds open (after backend
    # start-up): what tells one pinned process's chip from another's, since
    # each sees its own chip as device 0.
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if re.fullmatch(r"/dev/(accel|vfio/)\d+", target):
            held.add(target)
    # Which chip of the host: a pinned process sees its chip as device 0,
    # so the index is the one its launcher confined it to
    # (chip_environment); None where the process was not pinned to one.
    visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
    return {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "devices": [str(device) for device in devices],
        "device_files": sorted(held),
        "device_id": devices[0].id,
        "chip": int(visible) if visible.isdigit() else None,
    }


if __name__ == "__main__":
    # Shell launchers: `env $(python -m tpu_render_cluster.utils.accelerator 2) cmd`
    print(" ".join(f"{k}={v}" for k, v in chip_environment(int(sys.argv[1])).items()))
