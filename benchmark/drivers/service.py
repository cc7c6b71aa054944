"""The `service` traffic driver: jobs of several scene families offered to
ONE `master serve` in a closed loop, frames counted as their files land.

A run is the farm as a service, as a studio's shared pool runs it:
`master.main serve` on the host CPU, one `tpu-raytrace` worker on the chip
started with no scene and no shape on its command line, and this process as
the clients: it renders each job file from the family's own configuration
(template, shape, format, seeded first frame), adds the job's `[render]`
table, name and directory, and submits it over the JSON-lines control
plane; whenever `status` reports a job finished it submits the next of the
configuration's endless sequence, so `jobs_in_hand` jobs are always with
the service. It never imports JAX or the program.

What a second driver shares with the first (`drivers/backlog.py`), by
import and unedited: `say`, `scan_frames`, `cache_entries`, `scrape_all`,
`reduce_traces`, the deadlines, the kernel pattern and the rehearsal's
shape; `lib/launch.py` (probe, spawn, stop), `lib/check.py` (files and
images, through a `Cell` of the family's configuration), `lib/estimator.py`
and the readers. What is its own: the jobs and the loop that offers them,
the warm-up rule (jobs finished, every family among them), the files of
many directories, and `reference/plain_service.py`'s account of what has
to be on disk.

It fails within seconds of the worker's joining, exit 1, on a program that
cannot take the cell: one whose worker does not count
`worker_job_prepare_seconds` prepares no job when it is announced, and
would compile inside frames that other jobs' frames queue behind.

What `run` hands to the per-layer readers, as `run`: `backlog`'s keys
(`render` is the window's frames' own mean shape: `samples` the
frame-weighted mean, so `kernel_Mpaths_per_s` counts the paths that were
traced; `files` holds (frame, completion time, bytes) of every job's files
of the window) and

    jobs    [{"name", "family", "frames", "submitted_s", "finished_s"}] of the run,
            times from the run's start, `finished_s` None for a job in flight
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import tomllib
import urllib.request
from pathlib import Path

from benchmark.drivers.backlog import (
    DRAIN_SECONDS, KERNEL_PATTERN, REHEARSAL_RENDER, SETUP_SECONDS, TRACE_WRITE_SECONDS,
    WARMUP_SECONDS, cache_entries, reduce_traces, say, scan_frames, scrape_all,
)
from benchmark.lib import check, estimator, launch, manifest, readers, scrape
from benchmark.lib.launch import BenchFailure
from benchmark.lib.manifest import BENCH_DIR, ROOT, Cell
from benchmark.lib.peaks import chip_peaks
from benchmark.reference import plain_service

JOIN_SECONDS = 300  # the worker opens the chip and connects; nothing compiles before a job
NEEDS_SERIES = "worker_job_prepare_seconds"
FRAMES_ROOT = "frames"  # under the run's directory: the configuration's output_directory_format


@dataclasses.dataclass(frozen=True)
class Family:
    """One scene family of the service: the accepted configuration it is
    read from, as a `Cell` the checks take, and its seeded first frame."""

    name: str
    cell: Cell
    frames_per_job: int
    first: int

    @property
    def shape(self) -> dict:
        return self.cell.config["render"]


@dataclasses.dataclass
class Job:
    name: str
    family: Family
    first: int
    last: int
    spec: dict
    submitted_at: float | None = None
    finished_at: float | None = None
    seen: dict = dataclasses.field(default_factory=dict)  # file name -> (mtime, size)

    @property
    def directory(self) -> str:
        return f"{FRAMES_ROOT}/{self.name}"


def load_families(cell: Cell, seed: int, rehearse: bool) -> list[Family]:
    """The families of the configuration, each with the `Cell` of the
    accepted configuration it names: that configuration's file, template
    and check block as they are, the checked frames cut to the service
    configuration's count."""
    listed = {c["name"]: c for c in manifest.load_benchmark()["configs"]}
    families = []
    for entry in cell.config["families"]:
        if entry["config"] not in listed:
            raise BenchFailure(f"family {entry['family']}: no configuration {entry['config']!r}")
        config_file = ROOT / listed[entry["config"]]["file"]
        config = json.loads(config_file.read_text())
        frames = {**config["check"]["frames"], "count": cell.config["check"]["frames_per_family"]}
        config = {**config, "check": {**config["check"], "frames": frames}}
        if rehearse:
            samples = min(config["render"]["samples"], REHEARSAL_RENDER["samples"])
            config["render"] = {**REHEARSAL_RENDER, "samples": samples}
        start = config["frame_range_from"]
        families.append(Family(
            name=entry["family"],
            cell=dataclasses.replace(
                cell, config_name=entry["config"], config=config, config_dir=config_file.parent,
            ),
            frames_per_job=entry["frames_per_job"],
            first=start["first"] + check.mix(seed) % start["span"],
        ))
    return families


def job_stream(cell: Cell, families: list[Family]):
    """The endless sequence of jobs: the configuration's `sequence` of
    families again and again, each family's jobs consecutive ranges of its
    source job from the seeded first frame, wrapping to the source's first
    settled frame past its last."""
    by_name = {family.name: family for family in families}
    next_first = {family.name: family.first for family in families}
    strategy = "\n".join(f"{key} = {json.dumps(value)}" for key, value in cell.traffic["strategy"].items())
    index = 0
    while True:
        for family_name in cell.config["sequence"]:
            family = by_name[family_name]
            config = family.cell.config
            first = next_first[family_name]
            if first + family.frames_per_job - 1 > config["frames"]:
                first = config["frame_range_from"]["first"]
            last = first + family.frames_per_job - 1
            next_first[family_name] = last + 1
            index += 1
            name = cell.config["job_name_format"].format(family=family_name, index=index)
            text = (family.cell.config_dir / config["job_template"]).read_text()
            for token, value in (
                ("@FRAME_RANGE_FROM@", str(first)), ("@FRAME_RANGE_TO@", str(last)),
                ("@WORKERS@", str(cell.config["workers"])), ("@STRATEGY@", strategy),
            ):
                if token not in text:
                    raise BenchFailure(f"{config['job_template']}: no {token}")
                text = text.replace(token, value)
            job = tomllib.loads(text)
            job["job_name"] = name
            job["output_directory_path"] = cell.config["output_directory_format"].format(job_name=name)
            job["render"] = dict(family.shape)
            spec = {"job": job, "weight": cell.traffic["weight"], "priority": cell.traffic["priority"]}
            yield Job(name=name, family=family, first=first, last=last, spec=spec)


def control(port: int, request: dict, timeout: float = 10.0) -> dict:
    """One request over the scheduler's JSON-lines control plane."""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as connection:
        connection.sendall(json.dumps(request).encode() + b"\n")
        with connection.makefile("rb") as reader:
            line = reader.readline()
    if not line:
        raise BenchFailure(f"control plane closed the connection on {request.get('op')!r}")
    response = json.loads(line)
    if not response.get("ok"):
        raise BenchFailure(f"control plane refused {request.get('op')!r}: {response.get('error')}")
    return response


class ClosedLoop:
    """`jobs_in_hand` jobs always with the service: the next is submitted
    when one is reported finished."""

    def __init__(self, stream, control_port: int, run_dir: Path, in_hand: int) -> None:
        self._stream = stream
        self._port = control_port
        self._run_dir = run_dir
        self._in_hand = in_hand
        self.jobs: list[Job] = []
        self._ids: dict[str, Job] = {}  # job_id -> job in flight

    def _submit(self) -> None:
        job = next(self._stream)
        job.submitted_at = time.time()
        self._ids[control(self._port, {"op": "submit", "spec": job.spec})["job_id"]] = job
        self.jobs.append(job)

    def poll(self) -> None:
        """Note every new file, ask which jobs have finished, refill."""
        if self._ids:
            views = control(self._port, {"op": "status"})["sched"]["jobs"]
            for job_id, job in list(self._ids.items()):
                status = views[job_id]["status"]
                if status == "cancelled":
                    raise BenchFailure(f"the service cancelled {job.name}")
                if status == "finished":
                    job.finished_at = time.time()
                    del self._ids[job_id]
        while len(self._ids) < self._in_hand:
            self._submit()
        self.scan()

    def scan(self) -> None:
        for job in self.jobs:
            wanted = job.last - job.first + 1
            if len(job.seen) < wanted:
                scan_frames(self._run_dir / job.directory, job.family.cell.config["output"]["extension"], job.seen)

    def finished(self) -> list[Job]:
        return [job for job in self.jobs if job.finished_at is not None]

    def newest_file(self) -> float | None:
        return max((mtime for job in self.jobs for mtime, _ in job.seen.values()), default=None)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, started_at: float, rehearse: bool) -> dict:
    """One run of one cell; returns the result line. A run that cannot
    stand for a measurement raises BenchFailure instead."""
    env = {**os.environ, "PYTHONPATH": str(ROOT)}
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["TRC_PALLAS"] = "1"  # the chip's kernels and random streams, interpreted
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


def _wait_for_worker(processes: launch.Processes, control_port: int, telemetry: int) -> None:
    """Until the worker has joined the service; then hold the program to
    what the cell needs of it."""
    deadline = time.monotonic() + JOIN_SECONDS
    while True:
        processes.check_alive("join")
        try:
            if control(control_port, {"op": "status"}, timeout=2.0)["sched"]["total_slots"] > 0:
                break
        except (OSError, BenchFailure):
            pass  # the master is not listening yet
        if time.monotonic() > deadline:
            raise BenchFailure("join: the worker did not connect to the service in time")
        time.sleep(0.05)
    with urllib.request.urlopen(f"http://127.0.0.1:{telemetry}/metrics", timeout=5.0) as reply:
        exposition = reply.read().decode("utf-8", "replace")
    if NEEDS_SERIES not in exposition:
        raise BenchFailure(
            f"this program's worker has no {NEEDS_SERIES}: it prepares no job when the service "
            "announces it, so it cannot take a cell whose worker learns scenes and shapes from jobs"
        )


def _run_in(
    cell: Cell, run_dir: Path, processes: launch.Processes, env: dict[str, str],
    device: dict, *, seed: int, seconds: float, trace: bool,
    started_at: float, rehearse: bool,
) -> dict:
    config, traffic, workers = cell.config, cell.traffic, cell.config["workers"]
    families = load_families(cell, seed, rehearse)
    master_port, control_port = launch.free_port(), launch.free_port()
    master_telemetry, worker_telemetry = launch.free_port(), launch.free_port()

    processes.spawn(
        [sys.executable, "-m", "tpu_render_cluster.master.main",
         "--host", "127.0.0.1", "--port", str(master_port),
         "--telemetryPort", str(master_telemetry),
         "serve", "--controlPort", str(control_port),
         "--resultsDirectory", str(run_dir / "results"), "--baseDirectory", str(run_dir)],
        run_dir / "master.log", {**env, "JAX_PLATFORMS": "cpu"}, ROOT,
    )
    worker_env = {**env, "BENCH_TRACE": "1" if trace else "0"}
    if not rehearse:
        worker_env.update(launch.chip_environment(0))
    # No --warmScene, --renderSize or --renderSamples: the worker learns
    # families and shapes from the jobs the service announces.
    worker_process = processes.spawn(
        [sys.executable, str(BENCH_DIR / "lib" / "worker_entry.py"),
         "--bench-index", "0", "--bench-dir", str(run_dir),
         "--masterServerHost", "127.0.0.1", "--masterServerPort", str(master_port),
         "--baseDirectory", str(run_dir), "--backend", "tpu-raytrace",
         "--telemetryPort", str(worker_telemetry), "--telemetryHost", "127.0.0.1"],
        run_dir / "worker-0.log", worker_env, ROOT,
    )
    _wait_for_worker(processes, control_port, worker_telemetry)
    say("joined", after_s=time.time() - started_at)

    loop = ClosedLoop(job_stream(cell, families), control_port, run_dir, traffic["jobs_in_hand"])
    poll_s = traffic["poll_seconds"]

    # Set-up ends when the first frame file of any job is whole on disk.
    deadline = time.monotonic() + SETUP_SECONDS
    while loop.newest_file() is None:
        processes.check_alive("set-up")
        if time.monotonic() > deadline:
            raise BenchFailure("set-up: no frame within the deadline")
        loop.poll()
        time.sleep(0.02)
    setup_s = min(mtime for job in loop.jobs for mtime, _ in job.seen.values()) - started_at
    say("setup", setup_s=setup_s, cache_entries=cache_entries())

    # Warm-up: until so many jobs have finished, every family among them:
    # every program is resident and the loop has reached its mix.
    deadline = time.monotonic() + WARMUP_SECONDS + SETUP_SECONDS  # the second family may still compile
    while True:
        processes.check_alive("warm-up")
        loop.poll()
        done = loop.finished()
        if len(done) >= traffic["warmup_jobs"] and (
            not traffic["warmup_every_family"] or {job.family.name for job in done} == {f.name for f in families}
        ):
            break
        if time.monotonic() > deadline:
            raise BenchFailure(f"warm-up: {len(done)} jobs finished, want {traffic['warmup_jobs']} of every family")
        time.sleep(poll_s)

    # The window begins in a lull, as `backlog`'s does.
    lull_deadline = time.monotonic() + 1.0
    while time.monotonic() < lull_deadline:
        loop.scan()
        if time.time() - loop.newest_file() >= 0.25:
            break
        time.sleep(0.02)
    before = {"master": scrape_all([master_telemetry]), "workers": scrape_all([worker_telemetry])}
    entries_before = cache_entries()
    window_start = time.time()
    window_end = window_start + seconds
    slice_s = min(float(config["trace_slice_s"]), seconds / 2.0)
    trace_at = window_start + (seconds - slice_s) / 2.0 if trace else None
    while time.time() < window_end:
        processes.check_alive("window")
        if trace_at is not None and time.time() >= trace_at:
            (run_dir / "trace-0.go").write_text(str(slice_s))
            trace_at = None
        loop.poll()
        time.sleep(min(poll_s, max(0.0, window_end - time.time())))
    after = {"master": scrape_all([master_telemetry]), "workers": scrape_all([worker_telemetry])}
    scraped_at = time.time()
    entries_after = cache_entries()
    time.sleep(0.05)  # a file renamed at the edge shows in the next scan
    loop.poll()

    if trace:
        deadline = time.monotonic() + TRACE_WRITE_SECONDS
        while not (run_dir / "trace-0.done").exists():
            processes.check_alive("trace")
            if time.monotonic() > deadline:
                raise BenchFailure("trace: the worker did not finish writing its trace")
            loop.poll()  # the service goes on serving while the profile is written
            time.sleep(poll_s)

    # Stop: which jobs the service reported finished is asked once more,
    # then the worker drains (the frame in hand, its spans and snapshot)
    # and the service, which would wait for its jobs for ever, is ended.
    loop.poll()
    finished = {job.name for job in loop.finished()}
    codes = processes.terminate([worker_process], DRAIN_SECONDS)
    processes.kill_all()
    say("stopped", worker_exit_codes=codes)
    loop.scan()  # a frame finished in the drain is on disk too

    in_window = [
        (job, name, mtime, size) for job in loop.jobs for name, (mtime, size) in job.seen.items()
        if window_start < mtime <= window_end
    ]
    times = [mtime for _, _, mtime, _ in in_window]
    frames_per_s = estimator.slope_rate(times)
    by_family = {
        family.name: [mtime for job, _, mtime, _ in in_window if job.family is family] for family in families
    }
    jobs_in_window = [job for job in loop.finished() if window_start < job.finished_at <= window_end]
    say(
        "window", seconds=seconds, files=len(in_window), frames_per_s=frames_per_s, traced=trace,
        families={
            name: {
                "files": len(stamps), "frames_per_s": estimator.slope_rate(stamps),
                "jobs_finished": sum(1 for job in jobs_in_window if job.family.name == name),
            } for name, stamps in by_family.items()
        },
        jobs_submitted=len(loop.jobs), jobs_finished=len(finished),
        per_second=estimator.per_second(times, window_start, seconds),
    )
    if frames_per_s is None:
        raise BenchFailure(f"only {len(in_window)} frames completed inside the window")
    # Where a frame's time went, per frame of the window: detail that an
    # untraced run has too (a traced line carries the step metrics, which
    # list this cell since PR 44).
    frames = scrape.delta(
        before["workers"], after["workers"], "worker_frame_phase_seconds_count", {"phase": "render"}
    )
    if frames:
        def ms_per_frame(series: str, **labels: str) -> float | None:
            value = scrape.delta(before["workers"], after["workers"], series, labels)
            return None if value is None else 1000.0 * value / frames
        say(
            "steps", frames=frames,
            **{name: ms_per_frame("worker_frame_step_seconds_sum", step=name) for name in
               ("resolve", "dispatch", "device_wait", "readback", "encode", "file_write")},
            **{name: ms_per_frame("worker_loop_seconds_total", state=state) for name, state in
               (("starved", "no_work"), ("report", "report"))},
        )

    # Outcomes known inside the window, and the checks that decide `correct`.
    problems: list[str] = []
    errored = int(scrape.delta(before["workers"], after["workers"], "worker_frames_errored_total") or 0)
    bad_files = 0
    for job in loop.jobs:  # per (job, frame): every job's files against its own shape and range
        paths = [run_dir / job.directory / name for j, name, _, _ in in_window if j is job]
        if paths:
            bad, file_problems = check.check_files(
                paths, width=job.family.shape["width"], height=job.family.shape["height"],
                first_frame=job.first, last_frame=job.last,
                decode_at_most=max(1, 256 * len(paths) // len(in_window)),
            )
            bad_files += bad
            problems += file_problems[:5]
    missing = [name for name, stamps in by_family.items() if not stamps]
    if missing:
        problems.append(f"no frame of {missing} landed inside the window")

    # The service's semantics: the tree against the plain reference.
    described = [
        {"name": job.name, "directory": job.name, "first": job.first, "last": job.last,
         "name_format": job.spec["job"]["output_file_name_format"],
         "file_format": job.spec["job"]["output_file_format"],
         "width": job.family.shape["width"], "height": job.family.shape["height"]}
        for job in loop.jobs
    ]
    must, may = plain_service.expected(described, finished)
    service_problems = plain_service.compare(run_dir / FRAMES_ROOT, must, may)
    problems += service_problems[:10]
    say("service", must=len(must), may=len(may), problems=len(service_problems))
    say("prepared", **{
        family.name: {
            key: scrape.total(after["workers"][0], f"worker_job_prepare_seconds_{key}", {"family": family.name})
            for key in ("sum", "count")
        } for family in families
    })

    rendered = scrape.delta(before["workers"], after["workers"], "worker_frames_rendered_total") or 0
    late = sum(1 for job in loop.jobs for mtime, _ in job.seen.values() if window_end < mtime <= scraped_at)
    slack = 2 * workers + late + 0.02 * len(in_window)  # frames in flight at the edges
    if abs(rendered - len(in_window)) > slack:
        problems.append(f"workers counted {rendered:.0f} frames rendered, {len(in_window)} files landed")
    early = scrape.delta(before["workers"], after["workers"], "worker_frames_before_ready_total")
    if early:
        problems.append(f"{early:.0f} frame(s) reached the render thread before their job was resident")
    snapshots = [json.loads(p.read_text()) for p in sorted((run_dir / "obs").glob("worker-*_metrics.json"))]
    if len(snapshots) != workers:
        problems.append(f"{len(snapshots)} worker snapshots, want {workers} (exit codes {codes})")
    stamps = [s.get("device", {}) for s in snapshots]
    if any(s.get("platform") != device["platform"] for s in stamps):
        problems.append(f"a worker rendered on another platform: {stamps}")
    held = [",".join(s.get("device_files", [])) for s in stamps]
    if not rehearse and "" in held:
        problems.append(f"the worker held no chip: {held}")
    say("workers", device_files=held, devices=[s.get("devices") for s in stamps])

    # Images: one frame a family by its configuration's own rule, found in
    # whichever of the family's jobs holds it.
    details = {}
    for family in families:
        files_by_frame = {
            check.frame_number(Path(name)): run_dir / job.directory / name
            for job in loop.jobs if job.family is family for name in job.seen
        }
        try:
            image_problems, family_details = check.check_images(
                family.cell, files_by_frame, f"{family.name}_svc", family.first,
                family.cell.config["frames"], seed, env,
            )
        except (RuntimeError, subprocess.TimeoutExpired) as error:
            image_problems, family_details = [f"image check could not run: {error}"], {}
        problems += [f"{family.name}: {problem}" for problem in image_problems]
        details[family.name] = family_details
    say("check", problems=problems, **details)

    memory = [
        json.loads(p.read_text()).get("peak_bytes_in_use") for p in sorted(run_dir.glob("device-*.json"))
    ]
    device_line = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": max((m for m in memory if m is not None), default=None),
    }
    shape = dict(families[0].shape)
    shape["samples"] = sum(job.family.shape["samples"] for job, *_ in in_window) / len(in_window)
    observed = {
        "window_s": seconds, "workers": workers, "frames_per_s": frames_per_s, "render": shape,
        "files": [(check.frame_number(Path(name)), mtime, size) for _, name, mtime, size in in_window],
        "scrapes": {key: (before[key], after[key]) for key in before},
        "cache_entries_delta": entries_after - entries_before, "trace": None,
        "jobs": [
            {"name": job.name, "family": job.family.name, "frames": job.last - job.first + 1,
             "submitted_s": job.submitted_at - started_at,
             "finished_s": None if job.finished_at is None else job.finished_at - started_at}
            for job in loop.jobs
        ],
    }
    result = {
        "correct": not problems, "attempted": len(in_window) + errored,
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
