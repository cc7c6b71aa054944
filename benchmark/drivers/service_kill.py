"""The `service_kill` traffic driver: the `service_pool` driver's pool, jobs
and closed loop, with ONE WORKER KILLED inside the window and not replaced.

A run is a studio's shared host on the day a worker process dies without
a word (the OOM killer, a crashed runtime, a preempted allocation) while a
dozen jobs are in hand: `master.main serve` on the host CPU, `workers`
`tpu-raytrace` worker processes, one a chip, this process as the clients;
`kill.at_s` seconds into the window the process group of worker
`mix(seed) mod workers` gets `kill.signal`, and the run goes on over the
others to the window's end, or until every job that was in hand at the kill
is reported finished, whichever is later, and never past `kill.settle_s`
after the kill.

What it shares, by import and unedited: from `drivers/service_pool.py` the
join, the closed loop that keeps the master's reports, the warm-up rule and
the checks of a family over a pool; from `drivers/service.py` the families,
the job stream and the control-plane client; from `drivers/backlog.py`
`say`, `scrape_all`, `reduce_traces`, `cache_entries` and the deadlines.
The configuration NAMES the pool configuration it is (`base`) and its
sequence, job formats, check block and deployment are read from that
configuration's file (`families` is in both, and has to be the same); a
job's `wait_for_number_of_workers` is the configuration's `job_barrier`,
not its `workers`.

What is its own:

- every worker is asked who it is (`/healthz`: the id the master knows it
  by) and which chip it holds (`/proc/<pid>/fd`, read from here: the dead
  one writes no snapshot) as the window begins, and scraped at the kill: the
  dead worker's last scrape stands for its edge of the window;
- the kill, its instant on the `window` line, and what the master says of
  the worker afterwards (`status`: `silent`, then `dead` with `ended_at`);
- the per-worker checks over the SURVIVORS, each by its own timeline, and
  one frame a family that the dead worker rendered before the kill, named by
  the master's record of results (`{"op": "results"}`);
- `reference/plain_failover.py`'s account of the jobs in hand at the kill,
  the units that were with the dead worker, every unit rendered twice and
  every path in a finished job's directory that is no frame of it;
- a traced run's profile slice is read from the survivors alone.

It fails within seconds of the workers' joining, exit 1, on a program that
cannot take the cell: what `service_pool` refuses, and a master that cannot
say whose result finished which unit (`{"op": "results"}`), so that the
renders of a worker that left no timeline could not be counted.

What `run` hands to the per-layer readers, as `run`: `service_pool`'s keys
(`scrapes["workers"]` pairs each worker's scrape as the window began with
its last: the survivors' as the window ended, the dead worker's at the
kill) and

    kill    {"worker", "index", "at", "evicted_at" (None if the master never said),
             "window_start", "window_end", "in_hand": [job names],
             "stranded": [(job, frame, back_at, file_at)],
             "survivor_scrapes": (at the kill, as the window ended),
             "files_after": [completion times of every file from the kill on]}
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

from benchmark.drivers.backlog import (
    DRAIN_SECONDS, KERNEL_PATTERN, SETUP_SECONDS, TRACE_WRITE_SECONDS, WARMUP_SECONDS,
    cache_entries, reduce_traces, say, scrape_all,
)
from benchmark.drivers.service import FRAMES_ROOT, control, job_stream, load_families
from benchmark.drivers.service_pool import (
    PoolLoop, _check_family, _pool_is_warm, _prepare_built_seconds, _wait_for_pool,
)
from benchmark.lib import check, estimator, launch, manifest, readers, scrape
from benchmark.lib.launch import BenchFailure
from benchmark.lib.manifest import BENCH_DIR, ROOT, Cell
from benchmark.lib.peaks import chip_peaks
from benchmark.reference import plain_failover, plain_service

SIGNALS = {"SIGKILL": signal.SIGKILL}
ASK_MASTER_EVERY_SECONDS = 1.0


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
            with_base(cell), run_dir, processes, env, device,
            seed=seed, seconds=seconds, trace=trace, started_at=started_at, rehearse=rehearse,
        )
    except BenchFailure:
        for log in sorted(run_dir.glob("*.log")):
            sys.stderr.write(f"--- {log.name} (tail)\n{log.read_text(errors='replace')[-3000:]}\n")
        raise
    finally:
        processes.kill_all()
        shutil.rmtree(run_dir, ignore_errors=True)


def with_base(cell: Cell) -> Cell:
    """The cell with what its configuration reads from the configuration it
    names under `base`: every key of that file that its own does not state
    (sequence, job sizes and formats, check block, deployment)."""
    listed = {c["name"]: c for c in manifest.load_benchmark()["configs"]}
    base = cell.config.get("base")
    if base not in listed:
        raise BenchFailure(f"{cell.config_name}: names no accepted configuration as its base ({base!r})")
    named = json.loads((ROOT / listed[base]["file"]).read_text())
    # `families` is in both files (the harness finds a service configuration's
    # templates through it): the copy has to be the base's.
    if cell.config.get("families", named["families"]) != named["families"]:
        raise BenchFailure(f"{cell.config_name}: its families are not those of its base {base}")
    return dataclasses.replace(cell, config={**named, **cell.config})


def _require_results(control_port: int) -> None:
    """Exit 1 on a master that cannot say whose result finished a unit."""
    try:
        control(control_port, {"op": "results", "job_id": "job-0000"})
    except BenchFailure as error:
        if "unknown op" in str(error):
            raise BenchFailure(
                "this program's master cannot say whose result finished which unit ({\"op\": \"results\"}): "
                "what a killed worker rendered, which leaves no timeline, could not be counted"
            ) from None


def _who(port: int) -> str:
    """The id the master knows the worker behind `port` by."""
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=5.0) as reply:
        return json.loads(reply.read())["worker_id"]


def _device_files(pid: int) -> list[str]:
    """The chip device files process `pid` holds open, as the worker's own
    stamp lists them (`utils/accelerator.py`), read from outside it."""
    held = set()
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if re.fullmatch(r"/dev/(accel|vfio/)\d+", target):
                held.add(target)
    except OSError:
        pass
    return sorted(held)


class KillLoop(PoolLoop):
    """The pool's loop, which also keeps each job's id: the master's record
    of results is asked for by it."""

    def __init__(self, stream, control_port: int, run_dir: Path, in_hand: int) -> None:
        super().__init__(stream, control_port, run_dir, in_hand)
        self.job_ids: dict[str, str] = {}  # job name -> job_id

    def _submit(self) -> None:
        super()._submit()
        job = self.jobs[-1]
        self.job_ids[job.name] = next(job_id for job_id, held in self._ids.items() if held is job)

    def in_hand(self) -> list:
        return [job for job in self.jobs if job.finished_at is None]

    def results(self) -> list[dict]:
        """The master's record of whose result finished which unit, over
        every job of the run."""
        out = []
        for job in self.jobs:
            reply = control(self._port, {"op": "results", "job_id": self.job_ids[job.name]})
            out += [{"job_name": job.name, **entry} for entry in reply["results"]]
        return out


def _other_paths(directory: Path, job) -> list[str]:
    """Every path under a job's directory that is no frame of its range."""
    wanted = {
        plain_service.file_name(job.spec["job"]["output_file_name_format"], frame, job.spec["job"]["output_file_format"])
        for frame in range(job.first, job.last + 1)
    }
    if not directory.is_dir():
        return []
    return sorted(
        str(path.relative_to(directory)) for path in directory.rglob("*")
        if not (path.parent == directory and path.name in wanted)
    )


def _run_in(
    cell: Cell, run_dir: Path, processes: launch.Processes, env: dict[str, str],
    device: dict, *, seed: int, seconds: float, trace: bool,
    started_at: float, rehearse: bool,
) -> dict:
    config, traffic, workers = cell.config, cell.traffic, cell.config["workers"]
    plan = traffic["kill"]
    if plan["signal"] not in SIGNALS or plan["replaced"]:
        raise BenchFailure(f"{plan}: this driver sends SIGKILL and replaces nobody")
    if not 0.0 < plan["at_s"] < seconds:
        raise BenchFailure(f"the kill at {plan['at_s']} s lies outside a window of {seconds} s")
    victim = check.mix(seed) % workers
    families = load_families(cell, seed, rehearse)
    master_port, control_port, master_telemetry = launch.free_port(), launch.free_port(), launch.free_port()
    worker_telemetry = [launch.free_port() for _ in range(workers)]

    master_process = processes.spawn(
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
    survivors = [index for index in range(workers) if index != victim]
    must_live = [master_process, *worker_processes]

    def check_alive(what: str) -> None:
        for process in must_live:
            code = process.poll()
            if code is not None:
                raise BenchFailure(f"{what}: {' '.join(process.args[1:4])} exited {code}")

    _wait_for_pool(processes, control_port, worker_telemetry, workers)
    _require_results(control_port)
    say("joined", after_s=time.time() - started_at, workers=workers)

    # A job states the barrier a shared service's client would: `job_barrier`.
    stream_cell = dataclasses.replace(cell, config={**config, "workers": config["job_barrier"]})
    loop = KillLoop(job_stream(stream_cell, families), control_port, run_dir, traffic["jobs_in_hand"])
    poll_s = traffic["poll_seconds"]

    # Set-up ends when the first frame file of any job is whole on disk.
    deadline = time.monotonic() + SETUP_SECONDS
    while loop.newest_file() is None:
        check_alive("set-up")
        if time.monotonic() > deadline:
            raise BenchFailure("set-up: no frame within the deadline")
        loop.poll()
        time.sleep(0.02)
    setup_s = min(mtime for job in loop.jobs for mtime, _ in job.seen.values()) - started_at
    say("setup", setup_s=setup_s, cache_entries=cache_entries())

    # Warm-up, as the pool's: so many jobs finished, every family among
    # them, every worker holding and having rendered every family.
    deadline = time.monotonic() + WARMUP_SECONDS + SETUP_SECONDS
    while True:
        check_alive("warm-up")
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

    # Who is who, while all four can still say: the id the master knows each
    # worker by, and the chip each holds.
    worker_ids = [_who(port) for port in worker_telemetry]
    held = [",".join(_device_files(process.pid)) for process in worker_processes]
    say("workers", ids=worker_ids, device_files=held, victim=victim)

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
    kill_due = window_start + plan["at_s"]
    slice_s = min(float(config["trace_slice_s"]), seconds / 2.0)
    trace_at = window_start + (seconds - slice_s) / 2.0 if trace else None
    killed_at = evicted_at = at_kill = after = scraped_at = entries_after = None
    said_of_victim: list[tuple[float, str]] = []  # (seconds after the kill, state), as it changes
    in_hand_at_kill: list = []
    asked = time.monotonic()

    def ask_master() -> None:
        nonlocal evicted_at
        view = control(control_port, {"op": "status"})["sched"].get("workers", {}).get(worker_ids[victim])
        if view is None:
            return
        if not said_of_victim or said_of_victim[-1][1] != view["state"]:
            said_of_victim.append((time.time() - killed_at, view["state"]))
        if view["state"] == "dead" and evicted_at is None:
            evicted_at = view.get("ended_at") or time.time()

    while True:
        now = time.time()
        check_alive("window")
        if killed_at is None and now >= kill_due:
            # Every worker's last word before the kill; then the kill.
            at_kill = scrape_all(worker_telemetry)
            loop.poll()
            in_hand_at_kill = loop.in_hand()
            must_live.remove(worker_processes[victim])
            os.killpg(worker_processes[victim].pid, SIGNALS[plan["signal"]])
            killed_at = time.time()
            say("killed", worker=victim, id=worker_ids[victim], after_s=killed_at - window_start,
                in_hand=[job.name for job in in_hand_at_kill])
        if trace_at is not None and now >= trace_at:
            for index in survivors:  # the dead worker's profile is lost with it
                (run_dir / f"trace-{index}.go").write_text(str(slice_s))
            trace_at = None
        if after is None and now >= window_end:
            after = {
                "master": scrape_all([master_telemetry]),
                "workers": scrape_all([worker_telemetry[index] for index in survivors]),
            }
            scraped_at = time.time()
            entries_after = cache_entries()
            time.sleep(0.05)  # a file renamed at the edge shows in the next scan
        loop.poll()
        if killed_at is not None and time.monotonic() - asked >= ASK_MASTER_EVERY_SECONDS:
            asked = time.monotonic()
            ask_master()
        if after is not None and (
            all(job.finished_at is not None for job in in_hand_at_kill)
            or time.time() >= killed_at + plan["settle_s"]
        ):
            break
        edges_ahead = [kill_due if killed_at is None else None, window_end if after is None else None]
        until = min((edge for edge in edges_ahead if edge is not None), default=time.time() + poll_s)
        time.sleep(min(poll_s, max(0.0, until - time.time())))
    settled_at = time.time()
    ask_master()

    if trace:
        deadline = time.monotonic() + TRACE_WRITE_SECONDS
        while not all((run_dir / f"trace-{index}.done").exists() for index in survivors):
            check_alive("trace")
            if time.monotonic() > deadline:
                raise BenchFailure("trace: a worker did not finish writing its trace")
            loop.poll()  # the service goes on serving while the profiles are written
            time.sleep(poll_s)

    # Stop: which jobs the service reported finished is asked once more;
    # the survivors drain (the frame in hand, their spans and snapshot), the
    # master is asked what it took back from whom and whose result finished
    # what, and the service, which would wait for its jobs for ever, is ended.
    loop.poll()
    finished = {job.name for job in loop.finished()}
    codes = processes.terminate([worker_processes[index] for index in survivors], DRAIN_SECONDS)
    loop.ask_handbacks()
    handbacks = loop.handbacks
    results = loop.results()
    ask_master()
    processes.kill_all()
    say("stopped", worker_exit_codes=codes, said_of_victim=said_of_victim)
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
        killed_after_s=killed_at - window_start, killed_worker=victim,
        evicted_after_s=None if evicted_at is None else evicted_at - killed_at,
        settled_after_s=settled_at - killed_at,
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

    # Each worker's edge of the window: the survivors' as it ended, the dead
    # worker's at the kill.
    last = list(at_kill)
    for index, one in zip(survivors, after["workers"]):
        last[index] = one
    edges = {"master": (before["master"], after["master"]), "workers": (before["workers"], last)}

    def increase(one: int, series: str, **labels: str) -> float:
        """Of one worker, over its part of the window."""
        return scrape.delta([before["workers"][one]], [last[one]], series, labels) or 0.0

    frames = scrape.delta(*edges["workers"], "worker_frame_phase_seconds_count", {"phase": "render"})
    if frames:
        def ms_per_frame(series: str, **labels: str) -> float | None:
            value = scrape.delta(*edges["workers"], series, labels)
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
            "frames": frames_by_worker[one], "killed": one == victim,
            "by_family": {f.name: increase(one, "worker_frames_rendered_by_family_total", family=f.name) for f in families},
            "no_work_s": increase(one, "worker_loop_seconds_total", state="no_work"),
            "no_work_s_after_kill": None if one == victim else scrape.delta(
                [at_kill[one]], [last[one]], "worker_loop_seconds_total", {"state": "no_work"}
            ),
        } for one in range(workers)
    ])

    # Outcomes known inside the window, and the checks that decide `correct`.
    problems: list[str] = []
    errored = int(scrape.delta(*edges["workers"], "worker_frames_errored_total") or 0)
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

    # The pool's, without one of its members: the survivors' own timelines,
    # the master's record of what the dead worker delivered, and its reports.
    timelines = {
        path.name.removesuffix("_trace-events.json").removeprefix("worker-"): path
        for path in sorted((run_dir / "obs").glob("worker-*_trace-events.json"))
    }
    survivor_ids = [worker_ids[index] for index in survivors]
    spans = {f"worker-{name}": plain_failover.rendered_spans(timelines[name]) for name in survivor_ids if name in timelines}
    whole_records = len(spans) == len(survivors) and all(units is not None for units in spans.values())
    if not whole_records:
        problems.append(
            f"{len(spans)} timelines of the {len(survivors)} survivors ({sorted(timelines)}), "
            "each has to name the job of every frame it rendered"
        )
    kill = {"worker": worker_ids[victim], "at": killed_at}
    described_for_failover = [
        {"name": job.name, "first": job.first, "last": job.last, "submitted_at": job.submitted_at,
         "finished_at": job.finished_at,
         "files": {check.frame_number(Path(name)): mtime for name, (mtime, _) in job.seen.items()},
         "other_paths": _other_paths(run_dir / job.directory, job)}
        for job in loop.jobs
    ]
    accounted = plain_failover.account(
        described_for_failover, kill, plan["settle_s"],
        {worker: units or [] for worker, units in spans.items()}, results, handbacks,
    )
    failover_problems = plain_failover.problems(accounted, kill, plan["settle_s"])
    problems += failover_problems[:10]
    if evicted_at is None:
        problems.append(
            f"the master never said worker {worker_ids[victim]} was dead (it said {said_of_victim or 'nothing'} "
            f"in the {settled_at - killed_at:.0f} s after the kill)"
        )
    causes: dict[str, int] = {}
    for report in handbacks:
        causes[report["cause"]] = causes.get(report["cause"], 0) + 1
    say(
        "failover", in_hand=accounted["in_hand"], stranded=accounted["stranded"], late=accounted["late"],
        rendered_twice={key: value[:20] for key, value in accounted["rendered_twice"].items()},
        leavings=accounted["leavings"][:20], handbacks=causes, problems=len(failover_problems),
    )
    prepare_built_s = [_prepare_built_seconds(timelines[name]) for name in survivor_ids if name in timelines]
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
    if len(snapshots) != len(survivors):
        problems.append(f"{len(snapshots)} worker snapshots, want the {len(survivors)} survivors' (exit codes {codes})")
    stamps = [s.get("device", {}) for s in snapshots]
    if any(s.get("platform") != device["platform"] for s in stamps):
        problems.append(f"a worker rendered on another platform: {stamps}")
    if not rehearse and (len(set(held)) != len(held) or "" in held):
        problems.append(f"workers did not hold distinct chips as the window began: {held}")

    # Images: one frame a family by its configuration's own rules, one frame
    # a family of every SURVIVOR by the same-stream rule, named by its own
    # timeline, and one frame a family that the dead worker rendered before
    # the kill, named by the master's record; the families side by side.
    rendered = None
    if whole_records:
        rendered = {worker: [(job, frame) for job, frame, _ in units] for worker, units in spans.items()}
        seen_at = {(job["name"], frame): at for job in described_for_failover for frame, at in job["files"].items()}
        rendered[f"worker-{worker_ids[victim]} (killed)"] = [
            (result["job_name"], result["frame"]) for result in results
            if result["worker"] == worker_ids[victim]
            and seen_at.get((result["job_name"], result["frame"]), killed_at) < killed_at
        ]
    details = {}
    with concurrent.futures.ThreadPoolExecutor(max_workers=len(families)) as pool:
        checks = {
            family.name: pool.submit(_check_family, family, loop.jobs, run_dir, rendered, seed, env)
            for family in families
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
        "scrapes": edges,
        "cache_entries_delta": entries_after - entries_before, "trace": None,
        "jobs": [
            {"name": job.name, "family": job.family.name, "frames": job.last - job.first + 1,
             "submitted_s": job.submitted_at - started_at,
             "finished_s": None if job.finished_at is None else job.finished_at - started_at}
            for job in loop.jobs
        ],
        "pool": {
            "frames_by_worker": frames_by_worker, "prepare_built_s": prepare_built_s,
            "rendered_twice": {key: len(value) for key, value in accounted["rendered_twice"].items()},
        },
        "kill": {
            "worker": worker_ids[victim], "index": victim, "at": killed_at, "evicted_at": evicted_at,
            "window_start": window_start, "window_end": window_end, "in_hand": accounted["in_hand"],
            "stranded": [(u["job"], u["frame"], u["back_at"], u["file_at"]) for u in accounted["stranded"]],
            "survivor_scrapes": ([at_kill[index] for index in survivors], after["workers"]),
            "files_after": sorted(
                mtime for job in loop.jobs for mtime, _ in job.seen.values() if mtime >= killed_at
            ),
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
        say("kill_metrics", **{
            name: readers.read_metric(name, observed) for name in (
                "kill_to_eviction_s", "stranded_units", "stranded_recover_s", "survivor_starved_s_max",
                "jobs_blocked_on_silent_s", "pool_frames_per_s_after_kill",
            )
        })
    else:
        # The survivors' profiles, numbered as `reduce_traces` counts them.
        apart = run_dir / "survivors"
        apart.mkdir()
        (apart / "obs").symlink_to(run_dir / "obs")
        for place, index in enumerate(survivors):
            for name in (f"trace-{index}", f"trace-{index}.done"):
                (apart / name.replace(f"trace-{index}", f"trace-{place}")).symlink_to(run_dir / name)
        observed["trace"], breakdown = reduce_traces(apart, len(survivors), env, KERNEL_PATTERN)
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
