#!/usr/bin/env python3
"""Prove the served path on the chip: `python3 chip_smoke.py`, no arguments.

Drives the system the way a user does — `master.main run-job` on the host
CPU plus one `worker.main --backend tpu-raytrace` process per chip, as
separate OS processes at the only width the repo serves (512x512, 8 spp,
4 bounces) — through two jobs derived from committed job files:

- job A: `04_very-simple`, 64 frames, tpu-batch (the sphere megakernel);
- job B: 16 frames of `03_physics-2-mesh` (the TLAS bounce kernel, one
  launch per bounce inside the frame's program).

Then frame 1 of three scenes is rendered at 64x64 2 spp through
`render.cli` twice, on the chip and in a CPU child running the same
Pallas kernels in interpret mode (same RNG streams), and the PNG pairs
must agree — a kernel that compiles and computes something else fails
here. Every stage has a hard deadline; on expiry every process group the
script started is killed.

This parent never imports JAX: a process that has touched JAX holds the
chip. It learns platform, device_kind and chip count from a probe child
that exits before anything else starts. It exits non-zero, printing no
result, unless the probe reports a TPU. One JSON line per stage goes to
stdout, then a summary line (seconds cold/warm, ending `"claim": null`);
the last line is the result object, exactly `{"ok": ..., "device":
{"platform", "kind", "count"}}` with the device as the probe's JAX
reported it — `"ok": false` and exit 1 if any stage failed. No gain is
claimed from any number here: seconds are counts, cold or warm as labelled.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback
from pathlib import Path

import numpy as np
from PIL import Image

from tpu_render_cluster.utils.accelerator import (
    DEFAULT_COMPILE_CACHE_DIR,
    chip_environment,
)

REPO = Path(__file__).resolve().parent
RUN_DIR = REPO / "chiprun_out" / "chip_smoke"
JOB_A = REPO / "blender-projects/04_very-simple/04_very-simple_64f-4w_tpu-batch_tpu-raytrace.toml"
JOB_B = REPO / "blender-projects/03_physics-2/03_physics-2-mesh_240f-8w_tpu-batch_tpu-raytrace.toml"
FRAMES_A = 64
FRAMES_B = 16
WIDTH = HEIGHT = 512  # the worker CLI's defaults; the frame shape is not cut
CLI_SCENES = ("04_very-simple", "02_physics-mesh", "03_physics-2-mesh")
CLI_SIZE, CLI_SAMPLES = 64, 2
# Chip vs CPU-interpret agreement: same kernels, same RNG streams, so the
# images differ only by the two back ends' float rounding — a few u8
# levels, plus the rare pixel whose path flipped at a float tie.
CLI_MAX_LEVELS, CLI_MIN_AGREE = 4, 0.99
TOTAL_SECONDS = 1140  # the contract's 1200 s, with room to report
PROBE_SECONDS, JOB_SECONDS, CLI_SECONDS = 180, 480, 360

_started = time.monotonic()
_live: list[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def _deadline(stage_seconds: float) -> float:
    return min(time.monotonic() + stage_seconds, _started + TOTAL_SECONDS)


def _spawn(argv: list[str], log: Path, env: dict[str, str]) -> subprocess.Popen:
    """Start a child in its own process group, output to ``log``."""
    log.parent.mkdir(parents=True, exist_ok=True)
    with open(log, "wb") as handle:
        process = subprocess.Popen(
            argv, stdout=handle, stderr=subprocess.STDOUT, env=env,
            cwd=REPO, start_new_session=True,
        )
    _live.append(process)
    return process


def _kill_all() -> None:
    for process in _live:
        if process.poll() is None:
            try:
                os.killpg(process.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            process.wait()
    _live.clear()


def _wait(processes: list[subprocess.Popen], deadline: float, what: str) -> None:
    """Wait for every process to exit 0; the first failure or the
    deadline kills everything this script started."""
    pending = list(processes)
    while pending:
        for process in list(pending):
            code = process.poll()
            if code is None:
                continue
            pending.remove(process)
            if code != 0:
                raise SmokeFailure(f"{what}: {process.args[:4]} exited {code}")
        if pending and time.monotonic() > deadline:
            raise SmokeFailure(f"{what}: deadline passed with {len(pending)} process(es) running")
        time.sleep(0.2)


def _child_env() -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    return env


def _cache_dir() -> Path:
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE_DIR)


def _cache_entries() -> int:
    directory = _cache_dir()
    return sum(1 for _ in directory.glob("*-cache")) if directory.is_dir() else 0


def probe() -> dict:
    """Ask a short-lived child what JAX sees; it has exited on return."""
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    try:
        result = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=_child_env(), timeout=PROBE_SECONDS,
        )
    except subprocess.TimeoutExpired:
        raise SmokeFailure("probe: JAX did not come up in time") from None
    if result.returncode != 0:
        raise SmokeFailure(f"probe: JAX failed to start:\n{result.stderr[-2000:]}")
    device = json.loads(result.stdout.strip().splitlines()[-1])
    if device["platform"] != "tpu":
        raise SmokeFailure(
            f"probe: JAX found no accelerator (platform {device['platform']!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    return device


def _derive_job(template: Path, frames: int, workers: int, out: Path) -> str:
    """The committed job file with only the frame count and the worker
    barrier rewritten; returns the job name."""
    text = template.read_text()
    text, n_frames = re.subn(r"(?m)^frame_range_to = \d+$", f"frame_range_to = {frames}", text)
    text, n_workers = re.subn(
        r"(?m)^wait_for_number_of_workers = \d+$",
        f"wait_for_number_of_workers = {workers}", text,
    )
    if (n_frames, n_workers) != (1, 1):
        raise SmokeFailure(f"{template.name}: could not rewrite frame range / worker count")
    out.write_text(text)
    return re.search(r'(?m)^job_name = "(.+)"$', text).group(1)


def run_job(stage: str, template: Path, frames: int, device: dict) -> dict:
    """One job through master + one worker per chip; returns its stage line."""
    workers = device["count"]
    job_dir = RUN_DIR / stage
    job_dir.mkdir(parents=True)
    job_name = _derive_job(template, frames, workers, job_dir / "job.toml")
    with socket.socket() as probe_socket:
        probe_socket.bind(("127.0.0.1", 0))
        port = probe_socket.getsockname()[1]
    entries_before = _cache_entries()
    started = time.monotonic()
    env = _child_env()
    master = _spawn(
        [sys.executable, "-m", "tpu_render_cluster.master.main",
         "--host", "127.0.0.1", "--port", str(port),
         "run-job", str(job_dir / "job.toml"),
         "--resultsDirectory", str(job_dir / "results")],
        job_dir / "master.log", env,
    )
    worker_processes = [
        _spawn(
            [sys.executable, "-m", "tpu_render_cluster.worker.main",
             "--masterServerHost", "127.0.0.1", "--masterServerPort", str(port),
             "--baseDirectory", str(job_dir), "--backend", "tpu-raytrace",
             "--warmScene", job_name],
            job_dir / f"worker-{chip}.log", {**env, **chip_environment(chip)},
        )
        for chip in range(workers)
    ]
    try:
        _wait([master, *worker_processes], _deadline(JOB_SECONDS), stage)
    finally:
        _kill_all()
    seconds = time.monotonic() - started

    # Raw trace: every frame exactly once, from the expected worker count.
    (raw_path,) = (job_dir / "results").glob("*_raw-trace.json")
    raw = json.loads(raw_path.read_text())
    if len(raw["worker_traces"]) != workers:
        raise SmokeFailure(f"{stage}: trace lists {len(raw['worker_traces'])} workers, want {workers}")
    rendered = sorted(
        trace["frame_index"]
        for worker in raw["worker_traces"].values()
        for trace in worker["frame_render_traces"]
    )
    if rendered != list(range(1, frames + 1)):
        raise SmokeFailure(f"{stage}: trace frames {rendered} are not 1..{frames} once each")
    idle_workers = [
        name for name, worker in raw["worker_traces"].items()
        if not worker["frame_render_traces"]
    ]
    if idle_workers:
        raise SmokeFailure(f"{stage}: workers rendered nothing: {idle_workers}")

    # Frame files: present, full shape, not flat. Checked frames beyond
    # the first are removed: what chiprun brings back is capped.
    frame_files = sorted(job_dir.glob("blender-projects/*/frames/rendered-*.png"))
    if len(frame_files) != frames:
        raise SmokeFailure(f"{stage}: {len(frame_files)} frame files, want {frames}")
    for index, path in enumerate(frame_files):
        with Image.open(path) as image:
            pixels = np.asarray(image.convert("RGB"))
        if pixels.shape != (HEIGHT, WIDTH, 3) or pixels.std() < 2.0:
            raise SmokeFailure(f"{stage}: {path.name} is {pixels.shape}, std {pixels.std():.2f}")
        if index:
            path.unlink()

    # Worker snapshots: no errored frame, rendered on a TPU, one distinct
    # chip per worker.
    snapshots = [json.loads(p.read_text()) for p in sorted((job_dir / "obs").glob("worker-*_metrics.json"))]
    if len(snapshots) != workers:
        raise SmokeFailure(f"{stage}: {len(snapshots)} worker snapshots, want {workers}")
    devices = []
    for snapshot in snapshots:
        errored = snapshot["metrics"].get("worker_frames_errored_total", {}).get("series", {})
        if sum(errored.values()) != 0:
            raise SmokeFailure(f"{stage}: worker_frames_errored_total = {errored}")
        stamp = snapshot["device"]
        if stamp["platform"] != device["platform"]:  # the probe's: "tpu"
            raise SmokeFailure(f"{stage}: a worker rendered on {stamp}")
        devices.append(f"{','.join(stamp['device_files'])}: {','.join(stamp['devices'])} ({stamp['device_kind']})")
    if len(set(devices)) != workers:
        raise SmokeFailure(f"{stage}: workers did not hold distinct chips: {devices}")
    (processed_path,) = (job_dir / "results").glob("*_processed-results.json")
    fallbacks = json.loads(processed_path.read_text())["scheduler"]["auction_greedy_fallbacks"]
    return {
        "stage": stage, "ok": True, "job": job_name, "workers": workers,
        "frames": frames, "seconds": round(seconds, 1),
        "cache_entries_before": entries_before, "cache_entries_after": _cache_entries(),
        "auction_greedy_fallbacks": fallbacks, "worker_devices": devices,
    }


def run_cli_pairs() -> dict:
    """Frame 1 of three scenes through render.cli on the chip and on the
    CPU in Pallas interpret mode; the PNG pairs must agree."""
    out = RUN_DIR / "cli"
    out.mkdir(parents=True)
    started = time.monotonic()

    def cli(scene: str, png: Path) -> list[str]:
        return [sys.executable, "-m", "tpu_render_cluster.render.cli",
                "--scene", scene, "--frame", "1", "--width", str(CLI_SIZE),
                "--height", str(CLI_SIZE), "--samples", str(CLI_SAMPLES),
                "--out", str(png)]

    chip_env = {**_child_env(), **chip_environment(0)}
    # The CPU children keep their cache apart from the chip's.
    cpu_env = {**_child_env(), "JAX_PLATFORMS": "cpu", "TRC_PALLAS": "1",
               "JAX_COMPILATION_CACHE_DIR": str(out / "cpu-cache")}
    deadline = _deadline(CLI_SECONDS)
    try:
        cpu_children = [
            _spawn(cli(scene, out / f"{scene}_cpu.png"), out / f"{scene}_cpu.log", cpu_env)
            for scene in CLI_SCENES
        ]
        for scene in CLI_SCENES:  # one process on the chip at a time
            _wait([_spawn(cli(scene, out / f"{scene}_chip.png"), out / f"{scene}_chip.log", chip_env)],
                  deadline, f"cli {scene} (chip)")
        _wait(cpu_children, deadline, "cli (cpu interpret)")
    finally:
        _kill_all()
    agreement = {}
    for scene in CLI_SCENES:
        with Image.open(out / f"{scene}_chip.png") as a, Image.open(out / f"{scene}_cpu.png") as b:
            chip = np.asarray(a.convert("RGB"), np.int16)
            cpu = np.asarray(b.convert("RGB"), np.int16)
        if chip.shape != (CLI_SIZE, CLI_SIZE, 3) or chip.shape != cpu.shape:
            raise SmokeFailure(f"cli {scene}: shapes {chip.shape} vs {cpu.shape}")
        close = (np.abs(chip - cpu).max(axis=-1) <= CLI_MAX_LEVELS).mean()
        agreement[scene] = round(float(close), 4)
        if close < CLI_MIN_AGREE or chip.std() < 2.0:
            raise SmokeFailure(
                f"cli {scene}: chip and CPU-interpret agree on {close:.4f} of pixels "
                f"(want >= {CLI_MIN_AGREE} within {CLI_MAX_LEVELS} levels); chip std {chip.std():.2f}"
            )
    return {
        "stage": "cli_pairs", "ok": True, "size": f"{CLI_SIZE}x{CLI_SIZE}x{CLI_SAMPLES}spp",
        "pixels_within_levels": CLI_MAX_LEVELS, "agreement": agreement,
        "seconds": round(time.monotonic() - started, 1),
    }


def run_stages(device: dict) -> None:
    if RUN_DIR.exists():
        shutil.rmtree(RUN_DIR)
    RUN_DIR.mkdir(parents=True)
    common = {
        "platform": device["platform"], "device_kind": device["kind"],
        "chips": device["count"], "cache_dir": str(_cache_dir()),
    }
    # The cold run leaves its seconds beside the cache it filled, so a run
    # that starts warm from that cache prints both.
    record_path = _cache_dir() / "chip_smoke_cold_seconds.json"
    start = "warm" if _cache_entries() else "cold"
    cold = json.loads(record_path.read_text()) if start == "warm" and record_path.exists() else {}
    seconds = {}
    for stage in (
        lambda: run_job("job_a", JOB_A, FRAMES_A, device),
        lambda: run_job("job_b", JOB_B, FRAMES_B, device),
        run_cli_pairs,
    ):
        line = stage()
        seconds[line["stage"]] = line["seconds"]
        line.update(common, start=start)
        if start == "warm":
            line["seconds_cold"] = cold.get(line["stage"])
        print(json.dumps(line), flush=True)
    if start == "cold":
        record_path.write_text(json.dumps(seconds))
    print(json.dumps({
        "stage": "summary", "ok": True, **common, "start": start,
        "seconds": round(time.monotonic() - _started, 1),
        "seconds_by_stage": seconds, "seconds_by_stage_cold": cold or None,
        "claim": None,
    }), flush=True)


def main() -> int:
    try:
        device = probe()  # no accelerator: no result line
    except SmokeFailure as failure:
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        return 1
    ok = True
    try:
        run_stages(device)
    except Exception as failure:  # any failed stage fails the smoke
        ok = False
        if not isinstance(failure, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
    finally:
        _kill_all()
    # The contract's last line: these keys and no others.
    print(json.dumps({"ok": ok, "device": {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
    }}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
