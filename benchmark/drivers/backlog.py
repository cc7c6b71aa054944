"""The `backlog` traffic driver: one job whose whole backlog is handed to
the cluster at the start, frames counted as their files land on disk.

A run is the served path as a user starts it: `master.main run-job` on the
host CPU and one `tpu-raytrace` worker process per chip, as separate OS
processes. This process never imports JAX. It sees the program from
outside: the frame files, the `/metrics` endpoints of master and workers,
and the files the workers export when they drain.

What a run needs of its job: alive from the first frame to the window's
end, and no longer (`child_exit`). The window's end is where the
measurement is made; in a traced run's tail, while the workers write their
profiles, a job that is done may end. Each configuration states the rate
its smallest backlog holds under that rule (`held_rate`).

What `run` hands to the per-layer readers (`lib/readers.py`), as `run`:

    window_s, workers, frames_per_s, render   the window, the cluster, the shape
    files                  [(frame, completion time, bytes)] of the window
    scrapes                {"master": (before, after), "workers": (before, after)},
                           each a list of scrapes, one per process
    cache_entries_delta    new entries of the compile cache inside the window
    trace                  None, or {"devices": [{"slice_s", "busy_s", "kernel_s"}]}
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import tomllib
from pathlib import Path

from benchmark.lib import check, estimator, launch, readers, scrape, trace_reduce
from benchmark.lib.launch import BenchFailure
from benchmark.lib.manifest import BENCH_DIR, ROOT, Cell, load_benchmark
from benchmark.lib.peaks import chip_peaks

SETUP_SECONDS = 1000  # a first run compiles; the contract allows it 1200 s in all
WARMUP_SECONDS = 240
TRACE_WRITE_SECONDS = 90
DRAIN_SECONDS = 90
JOB_END_SECONDS = 15  # from the master's exit at its job's end to its workers' own
# The profiler names a device operation by its HLO text; a Pallas kernel is
# a custom call whose target is the TPU's kernel entry.
KERNEL_PATTERN = r"tpu_custom_call"
REHEARSAL_RENDER = {"width": 64, "height": 64, "samples": 2, "max_bounces": 4}


def say(stage: str, **fields) -> None:
    """One JSON line of detail; the result is the last line and only it."""
    print(json.dumps({"stage": stage, **fields}), flush=True)


def render_job_file(cell: Cell, seed: int, out: Path) -> tuple[str, int, int]:
    """The job file the master gets: the configuration's template with the
    traffic file's strategy block and the seed's first frame. Returns the
    job name and the frame range."""
    config = cell.config
    start = config["frame_range_from"]
    first = start["first"] + check.mix(seed) % start["span"]
    last = config["frames"]

    # json.dumps quotes strings and leaves numbers bare: TOML reads both
    strategy = "\n".join(f"{key} = {json.dumps(value)}" for key, value in cell.traffic["strategy"].items())
    text = (cell.config_dir / config["job_template"]).read_text()
    for token, value in (
        ("@FRAME_RANGE_FROM@", str(first)), ("@FRAME_RANGE_TO@", str(last)),
        ("@WORKERS@", str(config["workers"])), ("@STRATEGY@", strategy),
    ):
        if token not in text:
            raise BenchFailure(f"{config['job_template']}: no {token}")
        text = text.replace(token, value)
    out.write_text(text)
    return tomllib.loads(text)["job_name"], first, last


def smallest_backlog(config: dict) -> int:
    """Frames of the job at the highest first frame a seed can draw."""
    start = config["frame_range_from"]
    return config["frames"] - (start["first"] + start["span"] - 1) + 1


def held_rate(config: dict, run_seconds: float) -> float | None:
    """The rate, in frames a second over the whole cluster, up to which the
    configuration's smallest backlog outlasts a run: what is left of it
    after the warm-up, over the lull, the window and a margin for the
    scrapes at the window's end. None where the configuration states no
    `holds_frames_per_s`."""
    stated = config.get("holds_frames_per_s")
    if stated is None:
        return None
    seconds = stated["lull_s"] + run_seconds + stated["margin_s"]
    return (smallest_backlog(config) - stated["warmup_frames"]) / seconds


def job_ended_message(
    cell: Cell, backlog: int, seen: dict[str, tuple[float, int]], window_start: float | None, seconds: float,
) -> str:
    """The one line a ledger's reader needs when a job has run out: how
    long the backlog was, when it ended and at what rate, and the rate the
    configuration says it holds."""
    if window_start is None:
        when = "before the window began"
    else:
        landed = [mtime for mtime, _ in seen.values() if mtime > window_start]
        into = max(landed, default=window_start) - window_start
        rate = f" at {len(landed) / into:.4g} frames/s" if into > 0 else ""
        when = f"{into:.1f} s into the window of {seconds:g} s{rate}"
    held = held_rate(cell.config, load_benchmark()["run_seconds"])
    holds = "states no holds_frames_per_s" if held is None else f"holds to {held:.4g} frames/s"
    return (
        f"the job's backlog of {backlog} frames ended {when}; "
        f"frame_range_from of {cell.config_name} {holds}"
    )


TAIL = "trace"  # the phase after the window's end, while the workers write their profiles


def child_exit(phase: str, code: int, job_done: bool) -> str | None:
    """What the exit of a child (the master or a worker) means for the run.
    None: no fault. "job_ended": the job's backlog ran out before the
    measurement was made. "fault": anything else.

    A `run-job` master exits 0 when its job is done, and its workers exit 0
    once they have answered its job-finished request (which may be before
    the master itself has written its results and left): so an exit code
    of 0 with every frame of the job on disk is the job's end. After the
    window's end the run needs the job no longer; before it, the run
    cannot stand for a measurement."""
    if code != 0 or not job_done:
        return "fault"
    return None if phase == TAIL else "job_ended"


def scan_frames(directory: Path, extension: str, seen: dict[str, tuple[float, int]]) -> None:
    """Note every new frame file with its completion time and size.
    `write_image` writes a temporary file and renames it, so a file under
    its final name is whole, and its mtime is when its last byte was
    written."""
    try:
        entries = os.scandir(directory)
    except FileNotFoundError:
        return
    with entries:
        for entry in entries:
            name = entry.name
            if name in seen or name.startswith(".") or not name.endswith(extension):
                continue
            try:
                status = entry.stat()
            except FileNotFoundError:
                continue
            seen[name] = (status.st_mtime, status.st_size)


def cache_entries() -> int:
    directory = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or ROOT / ".jax_cache")
    return sum(1 for _ in directory.glob("*-cache")) if directory.is_dir() else 0


def scrape_all(ports: list[int]) -> list[scrape.Scrape]:
    return [scrape.fetch(port) for port in ports]


def reduce_traces(
    run_dir: Path, workers: int, env: dict[str, str], kernel_pattern: str
) -> tuple[dict | None, dict | None]:
    """Each worker's profiler trace of the slice, reduced to the device's
    busy and kernel seconds, and the breakdown: the device operations that
    took most time and the idle gaps by what the host was doing."""
    devices, operations_all, gap_labels = [], [], {}
    worker_spans = [
        trace_reduce.worker_phase_spans(path)
        for path in sorted((run_dir / "obs").glob("worker-*_trace-events.json"))
    ]
    for index in range(workers):
        done = json.loads((run_dir / f"trace-{index}.done").read_text())
        if "error" in done:
            raise BenchFailure(f"worker {index}: profiler failed: {done['error']}")
        reduced_path = run_dir / f"trace-{index}.json"
        child = subprocess.run(
            [sys.executable, str(BENCH_DIR / "lib" / "trace_reduce.py"),
             str(run_dir / f"trace-{index}"), str(reduced_path)],
            env={**env, "JAX_PLATFORMS": "cpu"}, cwd=ROOT, capture_output=True, text=True, timeout=300,
        )
        if child.returncode != 0:
            raise BenchFailure(f"trace reduction failed:\n{child.stderr[-2000:]}")
        reduced = json.loads(reduced_path.read_text())
        say("trace_planes", worker=index, planes=reduced["planes"], mark_s=reduced["mark_s"])
        if not reduced["devices"] or reduced["mark_s"] is None:
            continue  # no device plane (a CPU rehearsal): nothing to read
        start, stop = done["start_wall_s"], done["stop_wall_s"]
        for device in reduced["devices"]:
            operations = trace_reduce.clip(
                trace_reduce.to_wall(
                    trace_reduce.device_operations(device), reduced["mark_s"], done["mark_wall_s"]
                ), start, stop,
            )
            if not operations:
                continue
            devices.append({
                "worker": index, "plane": device["name"], "slice_s": stop - start,
                "busy_s": trace_reduce.busy_seconds(operations),
                "kernel_s": trace_reduce.kernel_seconds(operations, kernel_pattern),
                "operations": len(operations),
            })
            operations_all.extend(operations)
            # Which exported timeline is this worker's cannot be told from
            # its name; it is the one whose render spans cover this
            # device's busy time.
            busy = trace_reduce.busy_union(operations)
            spans = max(
                (trace_reduce.clip(spans, start, stop) for spans in worker_spans),
                key=lambda spans: trace_reduce.covered_seconds(
                    [s for s in spans if s[0] == "render"], busy
                ),
                default=[],
            )
            gaps = trace_reduce.idle_gaps(operations, start, stop)
            for label, seconds in trace_reduce.label_gaps(gaps, spans, operations, count=64):
                gap_labels[label] = gap_labels.get(label, 0.0) + seconds
    if not devices:
        return None, None
    breakdown = {
        "device_ops": trace_reduce.top_operations(operations_all),
        "idle_gaps": [[k, v] for k, v in sorted(gap_labels.items(), key=lambda item: -item[1])[:10]],
    }
    return {"devices": devices}, breakdown


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, started_at: float, rehearse: bool) -> dict:
    """One run of one cell; returns the result line. A run that cannot
    stand for a measurement raises BenchFailure instead."""
    env = {**os.environ, "PYTHONPATH": str(ROOT)}
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["TRC_PALLAS"] = "1"  # the chip's kernels and random streams, interpreted
        cell = dataclasses.replace(cell, config={**cell.config, "render": REHEARSAL_RENDER})
    device = launch.probe(env)
    say("probe", **device)
    if not rehearse:
        if device["platform"] != "tpu":
            raise BenchFailure(f"JAX found no accelerator (platform {device['platform']!r})")
        try:
            chip_peaks(device["kind"])
        except KeyError as error:
            raise BenchFailure(str(error)) from None
    if device["count"] < cell.chips and not rehearse:
        raise BenchFailure(f"the cell needs {cell.chips} chips, JAX found {device['count']}")

    # Frames, logs and exports live outside the checkout, in the system's
    # temporary directory, and go when the run ends.
    run_dir = Path(tempfile.mkdtemp(prefix="trc-bench-"))
    processes = launch.Processes()
    try:
        return _run_in(
            cell, run_dir, processes, env, device,
            seed=seed, seconds=seconds, trace=trace, started_at=started_at, rehearse=rehearse,
        )
    except BenchFailure:
        for log in sorted(run_dir.glob("*.log")):
            sys.stderr.write(f"--- {log.name} (tail)\n{log.read_text(errors='replace')[-3000:]}\n")
        raise
    finally:
        processes.kill_all()
        shutil.rmtree(run_dir, ignore_errors=True)


def _run_in(
    cell: Cell, run_dir: Path, processes: launch.Processes, env: dict[str, str],
    device: dict, *, seed: int, seconds: float, trace: bool,
    started_at: float, rehearse: bool,
) -> dict:
    config, shape, workers = cell.config, cell.config["render"], cell.config["workers"]
    job_name, first_frame, last_frame = render_job_file(cell, seed, run_dir / "job.toml")
    frames_dir = run_dir / "frames"
    extension = config["output"]["extension"]
    master_port, master_telemetry = launch.free_port(), launch.free_port()
    worker_telemetry = [launch.free_port() for _ in range(workers)]

    master_process = processes.spawn(
        [sys.executable, "-m", "tpu_render_cluster.master.main",
         "--host", "127.0.0.1", "--port", str(master_port),
         "--telemetryPort", str(master_telemetry),
         "run-job", str(run_dir / "job.toml"),
         "--resultsDirectory", str(run_dir / "results"), "--baseDirectory", str(run_dir)],
        run_dir / "master.log", {**env, "JAX_PLATFORMS": "cpu"}, ROOT,
    )
    worker_processes = []
    for index in range(workers):
        worker_env = {**env, "BENCH_TRACE": "1" if trace else "0"}
        if not rehearse:
            worker_env.update(launch.chip_environment(index))
        worker_processes.append(processes.spawn(
            [sys.executable, str(BENCH_DIR / "lib" / "worker_entry.py"),
             "--bench-index", str(index), "--bench-dir", str(run_dir),
             "--masterServerHost", "127.0.0.1", "--masterServerPort", str(master_port),
             "--baseDirectory", str(run_dir), "--backend", "tpu-raytrace",
             "--warmScene", job_name,
             "--renderSize", f"{shape['width']}x{shape['height']}",
             "--renderSamples", str(shape["samples"]),
             "--telemetryPort", str(worker_telemetry[index]), "--telemetryHost", "127.0.0.1"],
            run_dir / f"worker-{index}.log", worker_env, ROOT,
        ))

    children = [("the master", master_process)]
    children += [(f"worker {index}", process) for index, process in enumerate(worker_processes)]
    backlog = last_frame - first_frame + 1
    seen: dict[str, tuple[float, int]] = {}
    window_start = None

    def check_children(phase: str) -> None:
        """Raise unless every child is alive or has left as `child_exit` allows."""
        for name, process in children:
            code = process.poll()
            if code is None:
                continue
            scan_frames(frames_dir, extension, seen)  # the verdict needs the files as they are now
            verdict = child_exit(phase, code, len(seen) >= backlog)
            if verdict == "job_ended":
                raise BenchFailure(job_ended_message(cell, backlog, seen, window_start, seconds))
            if verdict == "fault":
                raise BenchFailure(f"{phase}: {name} ({' '.join(process.args[1:4])}) exited {code}")

    # Set-up ends when the first frame file is whole on disk: the master
    # starts the job only once every worker has connected, and a worker
    # connects only after it has warmed the cell's scene and shape.
    deadline = time.monotonic() + SETUP_SECONDS
    while not seen:
        check_children("set-up")
        if time.monotonic() > deadline:
            raise BenchFailure("set-up: no frame within the deadline")
        time.sleep(0.02)
        scan_frames(frames_dir, extension, seen)
    setup_s = min(mtime for mtime, _ in seen.values()) - started_at
    say("setup", setup_s=setup_s, cache_entries=cache_entries())

    # Warm-up: completions are discarded until every worker has finished
    # its share (for the raypool, whole pool windows).
    warmup_frames = cell.traffic["warmup_frames_per_worker"]
    deadline = time.monotonic() + WARMUP_SECONDS
    while True:
        check_children("warm-up")
        done = [scrape.total(s, "worker_frames_rendered_total") or 0 for s in scrape_all(worker_telemetry)]
        if min(done) >= warmup_frames:
            break
        if time.monotonic() > deadline:
            raise BenchFailure(f"warm-up: workers finished {done} frames, want {warmup_frames} each")
        time.sleep(0.1)

    # The window begins in a lull: once no frame has landed for a quarter
    # of a second, or after a second at the latest (a steady stream has no
    # lull). Under the raypool the warm-up ends inside a burst of frames,
    # and a window that began there would count the burst's tail.
    lull_deadline = time.monotonic() + 1.0
    while time.monotonic() < lull_deadline:
        scan_frames(frames_dir, extension, seen)
        if time.time() - max(mtime for mtime, _ in seen.values()) >= 0.25:
            break
        time.sleep(0.02)
    before = {"master": scrape_all([master_telemetry]), "workers": scrape_all(worker_telemetry)}
    entries_before = cache_entries()
    window_start = time.time()
    window_end = window_start + seconds
    slice_s = min(float(config["trace_slice_s"]), seconds / 2.0)
    trace_at = window_start + (seconds - slice_s) / 2.0 if trace else None
    while time.time() < window_end:
        check_children("window")
        if trace_at is not None and time.time() >= trace_at:
            for index in range(workers):
                (run_dir / f"trace-{index}.go").write_text(str(slice_s))
            trace_at = None
        scan_frames(frames_dir, extension, seen)
        time.sleep(min(0.1, max(0.0, window_end - time.time())))
    try:
        after = {"master": scrape_all([master_telemetry]), "workers": scrape_all(worker_telemetry)}
    except (OSError, http.client.HTTPException) as error:
        check_children("window")  # a child that left as the window ended says why
        raise BenchFailure(f"window: no scrape at the window's end: {error}") from None
    scraped_at = time.time()
    entries_after = cache_entries()
    time.sleep(0.05)  # a file renamed at the edge shows in the next scan
    scan_frames(frames_dir, extension, seen)

    # The measurement is made. From here to the stop a job that is done may
    # end: the master and then its workers exit 0, and a worker that leaves
    # with its job still writes its profile first (`worker_entry.py`).
    master_left_at = None
    if trace:
        deadline = time.monotonic() + TRACE_WRITE_SECONDS
        waiting = list(range(workers))
        while waiting:
            check_children(TAIL)
            if master_left_at is None and master_process.poll() is not None:
                master_left_at = time.time()
            # Asked before the file is looked for: a worker writes its `.done`, then leaves.
            left = {index for index in waiting if worker_processes[index].poll() is not None}
            waiting = [index for index in waiting if not (run_dir / f"trace-{index}.done").exists()]
            gone = sorted(left.intersection(waiting))
            if gone:
                raise BenchFailure(f"trace: workers {gone} left without writing their profile")
            if time.monotonic() > deadline:
                raise BenchFailure("trace: a worker did not finish writing its trace")
            time.sleep(0.1)

    # Stop: the workers drain (finish the frame in hand, export their spans
    # and snapshot, exit); a run-job master whose workers left mid-job would
    # wait for ever, so it is ended next.
    if master_process.poll() == 0:  # the job is done: its workers are leaving by themselves
        deadline = time.monotonic() + JOB_END_SECONDS
        while time.monotonic() < deadline and any(p.poll() is None for p in worker_processes):
            time.sleep(0.1)
    codes = processes.terminate(worker_processes, DRAIN_SECONDS)
    master_code = master_process.poll()
    processes.kill_all()
    written = [
        json.loads(path.read_text()).get("written_wall_s") for path in sorted(run_dir.glob("trace-*.done"))
    ]
    say(
        "stopped", worker_exit_codes=codes, master_exit_code=master_code,
        master_left_s_after_window=None if master_left_at is None else master_left_at - window_end,
        profiles_written_s_after_window=[None if at is None else at - window_end for at in written],
    )
    scan_frames(frames_dir, extension, seen)  # frames finished in the drain are on disk too

    files = sorted(
        (check.frame_number(Path(name)), mtime, size, name)
        for name, (mtime, size) in seen.items() if window_start < mtime <= window_end
    )
    times = [mtime for _, mtime, _, _ in files]
    frames_per_s = estimator.slope_rate(times)
    say(
        "window", seconds=seconds, files=len(files), frames_per_s=frames_per_s, traced=trace,
        backlog=backlog, backlog_left=backlog - sum(1 for mtime, _ in seen.values() if mtime <= window_end),
        per_second=estimator.per_second(times, window_start, seconds),
    )
    if frames_per_s is None:
        raise BenchFailure(f"only {len(files)} frames completed inside the window")

    # Outcomes known inside the window, and the checks that decide `correct`.
    problems: list[str] = []
    errored = int(scrape.delta(before["workers"], after["workers"], "worker_frames_errored_total") or 0)
    bad_files, file_problems = check.check_files(
        [frames_dir / name for _, _, _, name in files], width=shape["width"],
        height=shape["height"], first_frame=first_frame, last_frame=last_frame,
    )
    problems += file_problems[:10]
    rendered = scrape.delta(before["workers"], after["workers"], "worker_frames_rendered_total") or 0
    late = sum(1 for mtime, _ in seen.values() if window_end < mtime <= scraped_at)
    slack = 2 * workers + late + 0.02 * len(files)  # frames in flight at the edges
    if abs(rendered - len(files)) > slack:
        problems.append(f"workers counted {rendered:.0f} frames rendered, {len(files)} files landed")
    snapshots = [json.loads(p.read_text()) for p in sorted((run_dir / "obs").glob("worker-*_metrics.json"))]
    if len(snapshots) != workers:
        problems.append(f"{len(snapshots)} worker snapshots, want {workers} (exit codes {codes})")
    stamps = [s.get("device", {}) for s in snapshots]
    if any(s.get("platform") != device["platform"] for s in stamps):
        problems.append(f"a worker rendered on another platform: {stamps}")
    held = [",".join(s.get("device_files", [])) for s in stamps]
    if not rehearse and (len(set(held)) != len(held) or "" in held):
        problems.append(f"workers did not hold distinct chips: {held}")
    say("workers", device_files=held, devices=[s.get("devices") for s in stamps])
    try:
        image_problems, details = check.check_images(
            cell, {check.frame_number(Path(name)): frames_dir / name for name in seen},
            job_name, first_frame, last_frame, seed, env,
        )
    except (RuntimeError, subprocess.TimeoutExpired) as error:
        image_problems, details = [f"image check could not run: {error}"], {}
    problems += image_problems
    say("check", problems=problems, **details)

    memory = [
        json.loads(p.read_text()).get("peak_bytes_in_use") for p in sorted(run_dir.glob("device-*.json"))
    ]
    device_line = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": max((m for m in memory if m is not None), default=None),
    }
    observed = {
        "window_s": seconds, "workers": workers, "frames_per_s": frames_per_s, "render": shape,
        "files": [(number, mtime, size) for number, mtime, size, _ in files],
        "scrapes": {key: (before[key], after[key]) for key in before},
        "cache_entries_delta": entries_after - entries_before, "trace": None,
    }
    result = {
        "correct": not problems, "attempted": len(files) + errored,
        "failed": errored + bad_files, "metrics": {}, "device": device_line,
    }
    if not trace:
        values = {"frames_per_s": frames_per_s, "setup_s": setup_s}
        for metric in cell.end_to_end:
            result["metrics"][metric["name"]] = {"value": values[metric["name"]], "unit": metric["unit"]}
        compiles = readers.read_metric("compiles_in_window", observed)
    else:
        observed["trace"], breakdown = reduce_traces(run_dir, workers, env, KERNEL_PATTERN)
        if observed["trace"]:
            devices = observed["trace"]["devices"]
            say("trace", devices=devices)
            device_line["busy_s"] = sum(d["busy_s"] for d in devices) / len(devices)
            device_line["window_s"] = sum(d["slice_s"] for d in devices) / len(devices)
            result["breakdown"] = breakdown
        elif not rehearse:
            raise BenchFailure("the traced slice holds no device operation")
        for metric in cell.per_layer:
            value = readers.read_metric(metric["name"], observed)
            if value is not None:
                result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
        compiles = result["metrics"].get("compiles_in_window", {}).get("value", 0)
    if compiles:
        raise BenchFailure(f"{compiles:.0f} program(s) compiled inside the measured window")
    return result
