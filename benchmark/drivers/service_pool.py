"""The `service_pool` traffic driver: the `service` driver's jobs and closed
loop offered to ONE `master serve` that feeds a POOL of workers, one a chip.

A run is the farm as a studio's shared host runs it by day:
`master.main serve` on the host CPU, `workers` `tpu-raytrace` worker
processes, worker *i* pinned to chip *i*, each started with no scene and no
shape on its command line, and this process as the clients. The service
announces every admitted job to every worker, every worker prepares every
family, and the scheduler spreads each job's frames over all of them.

What it shares, by import and unedited: from `drivers/service.py` the
families, the job stream, the closed loop and the control-plane client;
from `drivers/backlog.py` what that driver shares too (`say`,
`scrape_all`, `reduce_traces`, `cache_entries`, the deadlines); the
workers are spawned as `backlog._run_in` spawns them (index, chip
environment, a telemetry port each, `trace-<i>.go` for every worker).
What is its own: the join (every worker, not one; asked over one kept
connection, so that no port is spent while the workers start), the
warm-up rule (jobs finished AND every worker holding and having rendered
every family), the
checks over a pool (one frame a family by the family's own rules, and one
frame a family of EVERY worker by its same-stream rule: every worker
holds a copy of every program), and `reference/plain_pool.py`'s account of
the units two workers rendered, against the master's reports, which the
loop asks for as it goes (the master drops a job's with the job).

It fails within seconds of the workers' joining, exit 1, on a program that
cannot take the cell: one whose workers prepare no announced job
(`service.NEEDS_SERIES`), or whose master cannot say which units left a
worker without a result (`{"op": "handbacks"}` on the control plane), so
that a frame rendered twice for a cause could not be told from a fault.

What `run` hands to the per-layer readers, as `run`: `service`'s keys
(`scrapes["workers"]` holds one scrape a worker, in the workers' order) and

    pool    {"frames_by_worker": [frames rendered inside the window, a worker],
             "prepare_built_s": [seconds of the worker's preparations that built
                                 something, a worker; None where its timeline
                                 does not say which did],
             "rendered_twice": {"explained": n, "unexplained": n}}
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import http.client
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from collections import Counter
from pathlib import Path

from benchmark.drivers.backlog import (
    DRAIN_SECONDS, KERNEL_PATTERN, SETUP_SECONDS, TRACE_WRITE_SECONDS, WARMUP_SECONDS,
    cache_entries, reduce_traces, say, scrape_all,
)
from benchmark.drivers.service import (
    FRAMES_ROOT, JOIN_SECONDS, NEEDS_SERIES, ClosedLoop, control, job_stream, load_families,
)
from benchmark.lib import check, estimator, launch, readers, scrape
from benchmark.lib.launch import BenchFailure
from benchmark.lib.manifest import BENCH_DIR, ROOT, Cell
from benchmark.lib.peaks import chip_peaks
from benchmark.reference import plain_pool, plain_service


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


def _until_joined(processes: launch.Processes, control_port: int, workers: int) -> None:
    """Until `workers` workers have joined the service, asked over ONE
    connection, kept open. A worker binds its telemetry port only when it
    has opened its chip, a quarter of a minute after the port was chosen
    for it, and every connection closed here leaves its own port in
    TIME_WAIT for a minute: on a host whose stack hands the same range to
    `bind(0)` and to `connect()` (the chip machine's does: listeners and
    clients share 16000-65535, and a listener is refused a closed client's
    port), some hundreds of connections while four workers start are a
    chance in thirty that one of them finds its port taken and exits."""
    deadline = time.monotonic() + JOIN_SECONDS
    connection = reader = None
    try:
        while True:
            processes.check_alive("join")
            try:
                if connection is None:
                    connection = socket.create_connection(("127.0.0.1", control_port), timeout=2.0)
                    reader = connection.makefile("rb")
                connection.sendall(b'{"op": "status"}\n')
                if json.loads(reader.readline())["sched"]["rebalance"]["workers"] >= workers:
                    return
            except (OSError, ValueError, KeyError):
                # the master is not listening yet (a refused connection keeps
                # no port), or was slow to answer: begin again
                if connection is not None:
                    connection.close()
                connection = None
            if time.monotonic() > deadline:
                raise BenchFailure(f"join: fewer than {workers} workers connected to the service in time")
            time.sleep(0.05 if connection is not None else 0.25)
    finally:
        if connection is not None:
            connection.close()


def _wait_for_pool(
    processes: launch.Processes, control_port: int, telemetry: list[int], workers: int
) -> None:
    """Until every worker has joined the service; then hold the program to
    what the cell needs of it."""
    _until_joined(processes, control_port, workers)
    for port in telemetry:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5.0) as reply:
            if NEEDS_SERIES not in reply.read().decode("utf-8", "replace"):
                raise BenchFailure(
                    f"this program's worker has no {NEEDS_SERIES}: it prepares no job when the "
                    "service announces it, so it cannot take a cell whose workers learn scenes "
                    "and shapes from jobs"
                )
    try:
        control(control_port, {"op": "handbacks"})
    except BenchFailure as error:
        raise BenchFailure(
            f"this program's master cannot say which units left a worker without a result ({error}): "
            "a frame two workers rendered for a cause could not be told from one handed out twice"
        ) from None


class PoolLoop(ClosedLoop):
    """The closed loop, which also keeps the master's account of the units
    it took back: the master reports those of the jobs it lists and drops a
    job's with the job, so the loop asks as it goes, about once a second,
    for what is newer than the newest it has."""

    ASK_EVERY_SECONDS = 1.0

    def __init__(self, stream, control_port: int, run_dir: Path, in_hand: int) -> None:
        super().__init__(stream, control_port, run_dir, in_hand)
        self.handbacks: list[dict] = []
        self._asked = time.monotonic()

    def poll(self) -> None:
        super().poll()
        if time.monotonic() - self._asked >= self.ASK_EVERY_SECONDS:
            self.ask_handbacks()

    def ask_handbacks(self) -> None:
        self._asked = time.monotonic()
        since = self.handbacks[-1]["at"] if self.handbacks else None
        self.handbacks += control(self._port, {"op": "handbacks", "since": since})["handbacks"]


def _pool_is_warm(telemetry: list[int], families: list) -> bool:
    """Every worker holds every family's program and has rendered a frame
    of each: no preparation is left for the window. A worker that does not
    answer in time (its loop behind a preparation's lowering) is not warm
    yet; one that never answers runs into the warm-up's deadline."""
    try:
        scrapes = scrape_all(telemetry)
    except (OSError, http.client.HTTPException):
        return False
    for one in scrapes:
        if (scrape.total(one, "render_resident_program_units") or 0) < len(families):
            return False
        for family in families:
            rendered = scrape.total(one, "worker_frames_rendered_by_family_total", {"family": family.name})
            if not rendered:
                return False
    return True


def _prepare_built_seconds(timeline: Path) -> float | None:
    """Seconds of the `job_prepare` spans of the worker that wrote
    `timeline` that built something (`args.resident` false: not one that
    found its family resident, or waited for a preparation in hand); None
    where no such span says which it was."""
    document = json.loads(timeline.read_text())
    events = document["traceEvents"] if isinstance(document, dict) else document
    prepares = [
        event for event in events
        if event.get("ph") == "X" and event.get("name") == "job_prepare" and "resident" in (event.get("args") or {})
    ]
    if not prepares:
        return None
    return sum(event["dur"] for event in prepares if not event["args"]["resident"]) / 1e6


def _a_unit_of_each_worker(rendered: dict[str, list], on_disk: dict, first_choice: int) -> dict[str, tuple[str, int]]:
    """worker -> the (job, frame) of its own to check, from its own record
    of what it rendered and has on disk: as few distinct frame numbers over
    the pool as cover it, `first_choice` before any other, since every
    frame number costs a reference (a family that wraps renders a frame
    number in more than one job, on more than one worker)."""
    mine = {worker: [unit for unit in units if unit in on_disk] for worker, units in rendered.items()}
    chosen: dict[str, tuple[str, int]] = {}
    frame: int | None = first_choice
    while True:
        left = {worker: units for worker, units in mine.items() if worker not in chosen and units}
        if not left:
            return chosen
        if frame is None:  # the frame number most of the workers left have rendered
            held_by = Counter(number for units in left.values() for number in {unit[1] for unit in units})
            frame = min(held_by, key=lambda number: (-held_by[number], number))
        for worker, units in left.items():
            unit = next((unit for unit in units if unit[1] == frame), None)
            if unit is not None:
                chosen[worker] = unit
        frame = None


def _check_family(
    family, jobs: list, run_dir: Path, rendered: dict[str, list] | None, seed: int, env: dict[str, str],
) -> tuple[list[str], dict]:
    """`family`'s images over the pool. ONE frame by the rules of the
    family's own configuration, same-stream and independent, found in
    whichever of its jobs holds it, as the one-worker cell checks it; and,
    since here every worker holds a copy of the family's program, one frame
    that EACH worker rendered, by the same same-stream rule (crop by the
    seed, border, levels, share: unchanged): a worker that holds another
    program or shape than its jobs state fails it whichever frames the
    others rendered. A frame number other than the first has its reference
    in a directory of its own, so that the references are rendered side by
    side."""
    config, name = family.cell.config, f"{family.name}_svc"
    on_disk = {
        (job.name, check.frame_number(Path(file))): run_dir / job.directory / file
        for job in jobs if job.family is family for file in job.seen
    }
    files_by_frame = {unit[1]: path for unit, path in on_disk.items()}
    main_frame = check.checked_frames(family.first, config["frames"], config["check"]["frames"])[0]
    chosen = _a_unit_of_each_worker(rendered or {}, on_disk, main_frame)
    same_stream_alone = {
        "frames": {"after": 0, "quantum": 1, "step": 1, "count": 1}, "same_stream": config["check"]["same_stream"],
    }

    def of_a_worker(worker: str) -> tuple[list[str], dict]:
        job, frame = chosen[worker]
        path = on_disk[chosen[worker]]
        if frame == main_frame and path == files_by_frame[main_frame]:
            return [], {"job": job, "frame": frame, "checked": "as the family's frame"}
        directory = family.cell.config_name if frame == main_frame else f"{family.cell.config_name}/f{frame}"
        cell = dataclasses.replace(family.cell, config_name=directory, config={**config, "check": same_stream_alone})
        problems, details = check.check_images(cell, {frame: path}, name, frame, config["frames"], seed, env)
        share = details.get("same_stream", {}).get("agreement", {}).get(frame)
        return [f"{worker}: {problem}" for problem in problems], {"job": job, "frame": frame, "agreement": share}

    def of_a_frame(frame: int) -> tuple[list[str], dict]:
        """One after another, what shares one reference."""
        problems, details = [], {}
        if frame == main_frame:
            problems, details = check.check_images(family.cell, files_by_frame, name, family.first, config["frames"], seed, env)
        by_worker = details.setdefault("by_worker", {})
        for worker in sorted(worker for worker, unit in chosen.items() if unit[1] == frame):
            worker_problems, by_worker[worker] = of_a_worker(worker)
            problems += worker_problems
        return problems, details

    frames = sorted({main_frame} | {unit[1] for unit in chosen.values()})
    problems: list[str] = [
        f"{worker} rendered no frame of this family that is on disk"
        for worker in sorted(rendered or {}) if worker not in chosen
    ]
    details: dict = {"by_worker": {}}
    with concurrent.futures.ThreadPoolExecutor(max_workers=len(frames)) as pool:
        for frame_problems, frame_details in pool.map(of_a_frame, frames):
            problems += frame_problems
            details["by_worker"].update(frame_details.pop("by_worker"))
            details.update(frame_details)
    return problems, details


def _run_in(
    cell: Cell, run_dir: Path, processes: launch.Processes, env: dict[str, str],
    device: dict, *, seed: int, seconds: float, trace: bool,
    started_at: float, rehearse: bool,
) -> dict:
    config, traffic, workers = cell.config, cell.traffic, cell.config["workers"]
    families = load_families(cell, seed, rehearse)
    master_port, control_port, master_telemetry = launch.free_port(), launch.free_port(), launch.free_port()
    worker_telemetry = [launch.free_port() for _ in range(workers)]

    processes.spawn(
        [sys.executable, "-m", "tpu_render_cluster.master.main",
         "--host", "127.0.0.1", "--port", str(master_port),
         "--telemetryPort", str(master_telemetry),
         "serve", "--controlPort", str(control_port),
         "--resultsDirectory", str(run_dir / "results"), "--baseDirectory", str(run_dir)],
        run_dir / "master.log", {**env, "JAX_PLATFORMS": "cpu"}, ROOT,
    )
    worker_processes = []
    for index in range(workers):
        worker_env = {**env, "BENCH_TRACE": "1" if trace else "0"}
        if not rehearse:
            worker_env.update(launch.chip_environment(index))
        # No --warmScene, --renderSize or --renderSamples: a worker learns
        # families and shapes from the jobs the service announces.
        worker_processes.append(processes.spawn(
            [sys.executable, str(BENCH_DIR / "lib" / "worker_entry.py"),
             "--bench-index", str(index), "--bench-dir", str(run_dir),
             "--masterServerHost", "127.0.0.1", "--masterServerPort", str(master_port),
             "--baseDirectory", str(run_dir), "--backend", "tpu-raytrace",
             "--telemetryPort", str(worker_telemetry[index]), "--telemetryHost", "127.0.0.1"],
            run_dir / f"worker-{index}.log", worker_env, ROOT,
        ))
    _wait_for_pool(processes, control_port, worker_telemetry, workers)
    say("joined", after_s=time.time() - started_at, workers=workers)

    loop = PoolLoop(job_stream(cell, families), control_port, run_dir, traffic["jobs_in_hand"])
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

    # Warm-up: until so many jobs have finished, every family among them,
    # AND every worker holds every family and has rendered a frame of each:
    # the loop has reached its mix and nothing is left to prepare.
    deadline = time.monotonic() + WARMUP_SECONDS + SETUP_SECONDS  # the second family may still compile
    while True:
        processes.check_alive("warm-up")
        loop.poll()
        done = loop.finished()
        if (
            len(done) >= traffic["warmup_jobs"]
            and (not traffic["warmup_every_family"] or {job.family.name for job in done} == {f.name for f in families})
            and (not traffic["warmup_every_worker"] or _pool_is_warm(worker_telemetry, families))
        ):
            break
        if time.monotonic() > deadline:
            raise BenchFailure(
                f"warm-up: {len(done)} jobs finished, want {traffic['warmup_jobs']} of every family "
                "and every family rendered on every worker"
            )
        time.sleep(poll_s)
    say("warm", after_s=time.time() - started_at, jobs_finished=len(loop.finished()))

    # The window begins in a lull, as `backlog`'s does.
    lull_deadline = time.monotonic() + 1.0
    while time.monotonic() < lull_deadline:
        loop.scan()
        if time.time() - loop.newest_file() >= 0.25:
            break
        time.sleep(0.02)
    before = {"master": scrape_all([master_telemetry]), "workers": scrape_all(worker_telemetry)}
    entries_before = cache_entries()
    window_start = time.time()
    window_end = window_start + seconds
    slice_s = min(float(config["trace_slice_s"]), seconds / 2.0)
    trace_at = window_start + (seconds - slice_s) / 2.0 if trace else None
    while time.time() < window_end:
        processes.check_alive("window")
        if trace_at is not None and time.time() >= trace_at:
            for index in range(workers):
                (run_dir / f"trace-{index}.go").write_text(str(slice_s))
            trace_at = None
        loop.poll()
        time.sleep(min(poll_s, max(0.0, window_end - time.time())))
    after = {"master": scrape_all([master_telemetry]), "workers": scrape_all(worker_telemetry)}
    scraped_at = time.time()
    entries_after = cache_entries()
    time.sleep(0.05)  # a file renamed at the edge shows in the next scan
    loop.poll()

    if trace:
        deadline = time.monotonic() + TRACE_WRITE_SECONDS
        while not all((run_dir / f"trace-{index}.done").exists() for index in range(workers)):
            processes.check_alive("trace")
            if time.monotonic() > deadline:
                raise BenchFailure("trace: a worker did not finish writing its trace")
            loop.poll()  # the service goes on serving while the profiles are written
            time.sleep(poll_s)

    # Stop: which jobs the service reported finished is asked once more;
    # then the workers drain (the frame in hand, their spans and snapshot),
    # the master is asked what it took back from whom, and the service,
    # which would wait for its jobs for ever, is ended.
    loop.poll()
    finished = {job.name for job in loop.finished()}
    codes = processes.terminate(worker_processes, DRAIN_SECONDS)
    loop.ask_handbacks()
    handbacks = loop.handbacks
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

    def increase(one: int, series: str, **labels: str) -> float:
        """Of one worker, over the window."""
        return scrape.delta([before["workers"][one]], [after["workers"][one]], series, labels) or 0.0

    # Where a frame's time went, per frame of the window, over the pool, and
    # what each worker did: detail that an untraced run has too (a traced
    # line carries the step metrics, which list this cell since PR 44).
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
    frames_by_worker = [increase(one, "worker_frames_rendered_total") for one in range(workers)]
    say("pool", workers=[
        {
            "frames": frames_by_worker[one],
            "by_family": {f.name: increase(one, "worker_frames_rendered_by_family_total", family=f.name) for f in families},
            "no_work_s": increase(one, "worker_loop_seconds_total", state="no_work"),
            "switches": increase(one, "worker_program_switches_total"),
            "prepare_s": scrape.total(after["workers"][one], "worker_job_prepare_seconds_sum"),
            "prepared": scrape.total(after["workers"][one], "worker_job_prepare_seconds_count"),
        } for one in range(workers)
    ])

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

    # The pool's: no (job, frame) rendered twice without a cause the master
    # reported, from the workers' own timelines against the master's account.
    timelines = sorted((run_dir / "obs").glob("worker-*_trace-events.json"))
    rendered = {path.name.removesuffix("_trace-events.json"): plain_pool.rendered_units(path) for path in timelines}
    prepare_built_s = [_prepare_built_seconds(path) for path in timelines]
    files_on_disk = sum(len(job.seen) for job in loop.jobs)
    if len(rendered) != workers or any(units is None for units in rendered.values()):
        problems.append(f"{len(rendered)} worker timelines, want {workers}, each naming the job of every frame it rendered")
        explained, unexplained = [], []
    else:
        recorded = sum(len(units) for units in rendered.values())
        if recorded < files_on_disk:
            problems.append(f"the workers' timelines record {recorded} rendered frames, {files_on_disk} files are on disk")
        explained, unexplained = plain_pool.account(rendered, handbacks)
        for unit in unexplained[:5]:
            problems.append(
                f"{unit['job']} frame {unit['frame']} was rendered {unit['renders']} times "
                f"({', '.join(unit['by'])}) and the master reports {unit['causes'] or 'no cause'}"
            )
    causes: dict[str, int] = {}
    for report in handbacks:
        causes[report["cause"]] = causes.get(report["cause"], 0) + 1
    say("rendered_twice", explained=explained[:20], unexplained=unexplained[:20], handbacks=causes)
    say("prepared", built_s=prepare_built_s)

    rendered_frames = sum(frames_by_worker)
    late = sum(1 for job in loop.jobs for mtime, _ in job.seen.values() if window_end < mtime <= scraped_at)
    slack = 2 * workers + late + 0.02 * len(in_window)  # frames in flight at the edges
    if abs(rendered_frames - len(in_window)) > slack:
        problems.append(f"workers counted {rendered_frames:.0f} frames rendered, {len(in_window)} files landed")
    for one in range(workers):
        early = increase(one, "worker_frames_before_ready_total")
        if early:
            problems.append(f"worker {one}: {early:.0f} frame(s) reached the render thread before their job was resident")
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

    # Images: one frame a family by its configuration's own rules, found in
    # whichever of the family's jobs holds it, and one frame a family of
    # EVERY worker by the same-stream rule; the families side by side.
    whole_records = rendered if all(units is not None for units in rendered.values()) else None
    details = {}
    with concurrent.futures.ThreadPoolExecutor(max_workers=len(families)) as pool:
        checks = {
            family.name: pool.submit(
                _check_family, family, loop.jobs, run_dir, whole_records, seed, env,
            ) for family in families
        }
        for family_name, future in checks.items():
            try:
                image_problems, details[family_name] = future.result()
            except (RuntimeError, subprocess.TimeoutExpired) as error:
                image_problems, details[family_name] = [f"image check could not run: {error}"], {}
            problems += [f"{family_name}: {problem}" for problem in image_problems]
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
        "pool": {
            "frames_by_worker": frames_by_worker, "prepare_built_s": prepare_built_s,
            "rendered_twice": {"explained": len(explained), "unexplained": len(unexplained)},
        },
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
        # The service's own metrics as the one-worker cell names them, in
        # one place (all but `jobs_per_min` list this cell too since PR 44,
        # and are on the line).
        say("service_metrics", **{
            name: readers.read_metric(name, observed) for name in (
                "job_admit_ms_mean", "job_finish_ms_mean", "jobs_per_min", "job_prepare_s_mean",
                "program_switch_share", "resident_geometry_MB", "resident_programs", "dispatch_on_event_share",
            )
        })
    if compiles:
        raise BenchFailure(f"{compiles:.0f} program(s) compiled inside the measured window")
    return result
