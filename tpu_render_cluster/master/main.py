"""Master CLI entry point.

Flag surface matches the reference's clap parser (reference:
master/src/cli.rs:5-40, master/src/main.rs:275-338):
``master --host H --port P [--logFilePath F] run-job <job.toml>
--resultsDirectory D`` — plus the NEW ``serve`` subcommand running the
multi-job scheduler service (sched/manager.py): workers connect on
``--port`` as usual, jobs arrive over the JSON-lines control plane on
``--controlPort`` (``python -m tpu_render_cluster.sched.submit``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from datetime import datetime
from pathlib import Path

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.master.cluster import ClusterManager
from tpu_render_cluster.master.persist import (
    parse_worker_traces,
    print_results,
    run_file_prefix,
    save_cost_model,
    save_processed_results,
    save_raw_traces,
)
from tpu_render_cluster.obs import export_cluster_trace, write_metrics_snapshot
from tpu_render_cluster.utils.logging import initialize_console_and_file_logging


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="trc-master", description="Render cluster master")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=9901)
    parser.add_argument("--logFilePath", dest="log_file_path", default=None)
    parser.add_argument(
        "--ledger",
        dest="ledger_directory",
        default=None,
        help="Write-ahead job ledger directory (replicated control plane): "
        "job lifecycle + unit-finished transitions are journaled (fsync'd, "
        "segmented, snapshot-compacted) so a restarted or standby master "
        "replays them, re-adopts live workers, and fences stale traffic "
        "with a monotonic epoch. Defaults to the TRC_HA_LEDGER environment "
        "variable; omit both to run ledger-less (reference behavior).",
    )
    parser.add_argument(
        "--replicationPort",
        dest="replication_port",
        type=int,
        default=None,
        help="Stream the ledger's committed records to follower processes "
        "(python -m tpu_render_cluster.ha.replicate) on this TCP port, so "
        "a standby on ANOTHER host holds a promotable replica — no shared "
        "filesystem. 0 picks an ephemeral port. Requires --ledger (or "
        "TRC_HA_LEDGER); defaults to the TRC_HA_REPL_PORT environment "
        "variable; omit both to disable.",
    )
    parser.add_argument(
        "--telemetryPort",
        dest="telemetry_port",
        type=int,
        default=None,
        help="Serve live pull-based telemetry over HTTP on this port: "
        "/metrics (Prometheus text exposition), /healthz, /clusterz "
        "(the live cluster_view). 0 picks an ephemeral port (printed). "
        "Defaults to the TRC_OBS_PORT environment variable; omit both to "
        "disable. This is the live path — metrics-live.json stays for "
        "file-based consumers.",
    )
    subparsers = parser.add_subparsers(dest="command", required=True)
    run_job = subparsers.add_parser("run-job", help="Run a job to completion")
    run_job.add_argument("job_file_path")
    run_job.add_argument(
        "--resultsDirectory",
        dest="results_directory",
        default=None,
        help="Where raw traces + processed results are written. Defaults to "
        "the canonical results/cluster-runs directory "
        "(tpu_render_cluster/analysis/paths.py), which run_all reads with "
        "no arguments.",
    )
    run_job.add_argument(
        "--resume",
        action="store_true",
        help="Skip frames whose output files already exist (resume-by-scan; "
        "beyond-reference, SURVEY.md §5.4).",
    )
    run_job.add_argument(
        "--baseDirectory",
        dest="base_directory",
        default=".",
        help="%%BASE%% root used to resolve the output directory for --resume.",
    )
    serve = subparsers.add_parser(
        "serve",
        help="Run the multi-job scheduler service: jobs are submitted over "
        "the JSON-lines control port (python -m tpu_render_cluster.sched.submit) "
        "and multiplexed over the shared worker pool with weighted "
        "fair-share + preemption; the service exits after a drain request "
        "once every job has finished.",
    )
    serve.add_argument(
        "--controlPort",
        dest="control_port",
        type=int,
        default=9902,
        help="TCP port of the JSON-lines control plane (submit/status/cancel/drain).",
    )
    serve.add_argument(
        "--resultsDirectory",
        dest="results_directory",
        default=None,
        help="Where the service's obs artifacts + metrics-live.json land "
        "(defaults to the canonical results/cluster-runs directory).",
    )
    serve.add_argument(
        "--baseDirectory",
        dest="base_directory",
        default=None,
        help="%%BASE%% root for resolving tiled jobs' output directories "
        "on the MASTER (the assembly stitcher reads tile files and writes "
        "the final frames there).",
    )
    return parser


def resolved_telemetry_port(args: argparse.Namespace) -> int | None:
    """The CLI flag, else the ``TRC_OBS_PORT`` env default, else disabled."""
    from tpu_render_cluster.obs.http import resolve_telemetry_port

    return resolve_telemetry_port(args.telemetry_port, "TRC_OBS_PORT")


def open_ledger(args: argparse.Namespace):
    """``--ledger`` flag, else ``TRC_HA_LEDGER``, else None (no journal).

    Opening claims the directory for this incarnation: the epoch is
    bumped + persisted and any torn tail from a previous crash repaired
    before the first append."""
    from tpu_render_cluster.ha.ledger import JobLedger
    from tpu_render_cluster.obs import get_registry
    from tpu_render_cluster.utils.env import env_str

    directory = args.ledger_directory or env_str("TRC_HA_LEDGER")
    if not directory:
        return None
    # The CLI's managers default to the process-global registry, so the
    # ledger's append-latency histogram lands in the same /metrics.
    ledger = JobLedger.open(directory, metrics=get_registry())
    print(
        f"Job ledger at {directory}: epoch {ledger.epoch}, "
        f"{ledger.replay.records} record(s) replayed."
    )
    return ledger


async def start_replication(ledger, args: argparse.Namespace):
    """Start the ledger streaming-replication endpoint when configured
    (``--replicationPort`` flag, else ``TRC_HA_REPL_PORT``), or None."""
    from tpu_render_cluster.utils.env import env_int

    port = args.replication_port
    if port is None:
        port = env_int("TRC_HA_REPL_PORT", -1)
        if port < 0:
            return None
    if ledger is None:
        print(
            "warning: --replicationPort ignored: no ledger to replicate "
            "(pass --ledger or set TRC_HA_LEDGER).",
            file=sys.stderr,
        )
        return None
    from tpu_render_cluster.ha.replicate import ReplicationServer
    from tpu_render_cluster.obs import get_registry

    replication = ReplicationServer(
        ledger, host=args.host, port=port, metrics=get_registry()
    )
    await replication.start()
    print(
        f"Ledger replication streaming on {args.host}:{replication.port} "
        f"(epoch {ledger.epoch}); attach followers with "
        f"python -m tpu_render_cluster.ha.replicate --primary "
        f"{args.host}:{replication.port} --directory <replica-dir>."
    )
    return replication


async def serve_command(args: argparse.Namespace) -> int:
    from tpu_render_cluster.sched.control import ControlServer
    from tpu_render_cluster.sched.manager import JobManager

    if args.results_directory is None:
        from tpu_render_cluster.analysis.paths import DEFAULT_RESULTS_DIR

        args.results_directory = str(DEFAULT_RESULTS_DIR)
    results_directory = Path(args.results_directory)
    ledger = await asyncio.to_thread(open_ledger, args)
    manager = JobManager(
        args.host,
        args.port,
        metrics_snapshot_path=results_directory / "metrics-live.json",
        output_base_directory=args.base_directory,
        telemetry_port=resolved_telemetry_port(args),
        ledger=ledger,
    )
    if ledger is not None:
        # Re-admit what a previous incarnation left unfinished: the jobs
        # re-enter the admission queue with their recorded weight/priority
        # and pick up at the ledger's finished set when admitted.
        from tpu_render_cluster.sched.models import JobSpec

        for entry in ledger.replay.unfinished_jobs():
            if entry.job is None:
                print(
                    f"warning: ledger job {entry.job_name!r} has no recorded "
                    "spec; cannot re-admit it.",
                    file=sys.stderr,
                )
                continue
            job_id = manager.submit(
                JobSpec(
                    job=BlenderJob.from_dict(entry.job),
                    weight=entry.weight,
                    priority=entry.priority,
                )
            )
            print(
                f"Ledger: re-admitted unfinished job {entry.job_name!r} "
                f"as {job_id} ({len(entry.finished_units)} unit(s) already "
                "finished)."
            )
    # A restarted service re-learns worker speeds from its own previous
    # shutdown snapshot (explicit TRC_COST_MODEL wins; saved again below).
    from tpu_render_cluster.sched.cost_model import (
        explicit_model_configured,
        load_model_snapshot,
        save_model_snapshot,
    )

    sched_model_path = results_directory / "sched_cost-model.json"
    if not explicit_model_configured():
        restored = load_model_snapshot(sched_model_path)
        if restored is not None:
            manager.cost_service.model = restored
    replication = await start_replication(ledger, args)
    control = ControlServer(manager, args.host, args.control_port)
    await control.start()
    print(
        f"Scheduler serving: workers on {args.host}:{args.port}, "
        f"control on {args.host}:{control.port}. Submit with "
        f"python -m tpu_render_cluster.sched.submit --host {args.host} "
        f"--controlPort {control.port} submit <job.toml>."
    )
    if manager.telemetry is not None:
        # The resolved (possibly ephemeral) port is logged by
        # TelemetryServer.start() once serve() binds.
        print(
            "Telemetry endpoints (once bound): /metrics /healthz /clusterz "
            f"on port {manager.telemetry.port or '<ephemeral>'}"
        )
    try:
        await manager.serve()
    finally:
        await control.stop()
        if replication is not None:
            await replication.stop()

        # Artifact export runs on FAILURE paths too (same pattern as the
        # assembly drain): a service that died mid-run is exactly the one
        # whose partial timeline and final ledger snapshot matter most.
        # Guarded per step so an export failure can neither mask the
        # service's real exception nor take the later writers down.
        def _save_model() -> None:
            # Final drain of completion observations (the last frames'
            # results can land after the scheduler loop's last ingest
            # tick).
            manager.cost_service.ingest(
                manager.workers.values(), manager._job_for_name
            )
            save_model_snapshot(manager.cost_service.model, sched_model_path)

        def _export_obs_artifacts() -> None:
            prefix = f"sched-{datetime.now().strftime('%Y-%m-%d_%H-%M-%S')}"
            manager.span_tracer.export(
                results_directory / f"{prefix}_trace-events.json"
            )
            export_cluster_trace(
                results_directory / f"{prefix}_cluster_trace-events.json",
                manager.cluster_timeline_processes(),
                extra_other_data=manager.timeline_other_data(),
            )
            write_metrics_snapshot(
                results_directory / f"{prefix}_metrics.json",
                manager.metrics,
                extra={
                    **manager.cluster_view(),
                    "history": manager.history.summary_dict(),
                },
            )

        for step in (_save_model, _export_obs_artifacts):
            try:
                step()
            except Exception as e:  # noqa: BLE001 - obs must not mask the run error
                print(
                    f"warning: obs artifact export failed: {e}",
                    file=sys.stderr,
                )
    view = manager.scheduler_view()
    print(json.dumps({"jobs": view["jobs"]}, indent=2, default=str))
    return 0


async def run_job_command(args: argparse.Namespace) -> int:
    if args.results_directory is None:
        from tpu_render_cluster.analysis.paths import DEFAULT_RESULTS_DIR

        args.results_directory = str(DEFAULT_RESULTS_DIR)
    job = BlenderJob.load_from_file(args.job_file_path)
    start_time = datetime.now()
    ledger = await asyncio.to_thread(open_ledger, args)
    manager = ClusterManager(
        args.host,
        args.port,
        job,
        metrics_snapshot_path=Path(args.results_directory) / "metrics-live.json",
        # Tiled jobs: the assembly stitcher resolves the job's %BASE%
        # output prefix with the same base directory resume does.
        output_base_directory=args.base_directory,
        telemetry_port=resolved_telemetry_port(args),
        ledger=ledger,
        ledger_resume=args.resume,
    )
    if args.resume:
        from tpu_render_cluster.master.resume import apply_resume, load_cost_model

        # Ledger wins (exact per-unit journal); the output-directory scan
        # is the fallback for jobs that predate the ledger. The manager
        # already applied any open-generation replay at construction;
        # apply_resume is idempotent over it.
        apply_resume(
            manager.state,
            job,
            args.base_directory,
            ledger_replay=ledger.replay if ledger is not None else None,
        )
        # Restore the previous run's learned predictors too (an explicit
        # TRC_COST_MODEL wins over the snapshot — load_cost_model
        # returns None when it is set).
        restored = load_cost_model(job, args.results_directory)
        if restored is not None:
            manager.cost_service.model = restored
        if manager.state.all_frames_finished():
            # Fully-resumed job: don't block on the worker barrier.
            from tpu_render_cluster.traces.master_trace import MasterTrace

            if ledger is not None:
                # Close the journal's lifecycle too: the crash this run
                # resumed from may have hit between the last unit append
                # and job_finished — leaving the entry "started" would
                # make every later replay re-admit a completed job.
                # Settle anything the manager's construction scheduled
                # BEFORE reading the lifecycle entry: a fresh generation's
                # job_started may still sit in the appender queue, and
                # reading first would skip the close below, leaving the
                # ledger "started" forever.
                if manager.ledger_appender is not None:
                    await manager.ledger_appender.stop()
                entry = ledger.replay.job(job.job_name)
                if entry is not None and entry.status == "started":
                    await asyncio.to_thread(
                        ledger.append_job_finished, job.job_name
                    )
                await asyncio.to_thread(ledger.close)
            print("All frames already rendered; nothing to do.")
            now = time.time()
            trace = MasterTrace(job_start_time=now, job_finish_time=now)
            results_directory = Path(args.results_directory)
            await asyncio.to_thread(
                save_raw_traces, start_time, job, results_directory, trace, []
            )
            # Keep the scheduler section present on every processed-results
            # file (consumers index it unconditionally); a fully-resumed
            # job scheduled nothing, so the count is trivially zero.
            await asyncio.to_thread(
                save_processed_results,
                start_time, job, results_directory, [],
                scheduler_stats={"auction_greedy_fallbacks": 0},
            )
            return 0
    from tpu_render_cluster.ops import assignment as assignment_ops

    assignment_ops.reset_greedy_fallback_count()
    results_directory = Path(args.results_directory)
    prefix = run_file_prefix(start_time, job)
    replication = await start_replication(ledger, args)
    try:
        master_trace, worker_traces = await manager.initialize_server_and_run_job()
    finally:
        if replication is not None:
            await replication.stop()
        # Obs artifacts are written even when the job RAISES (worker-pool
        # collapse, unit error budget, operator interrupt): the partial
        # span timeline, merged cluster trace, and final metrics/ledger
        # snapshot matter most in exactly those runs. Same pattern as the
        # assembly drain-on-failure. The prefix matches the raw trace the
        # success path writes below. Each writer is guarded independently:
        # an export failure (full disk, revoked permissions) must neither
        # mask the job's real exception nor take the later writers down.
        def _export_obs_artifacts() -> None:
            manager.span_tracer.export(
                results_directory / f"{prefix}_trace-events.json"
            )
            # Merged cluster timeline: the workers' span events
            # (piggybacked on their job-finished responses) rebased onto
            # the master clock by the heartbeat clock-offset estimates —
            # one Perfetto file with a process row per worker and flow
            # arrows per frame lifecycle.
            export_cluster_trace(
                results_directory / f"{prefix}_cluster_trace-events.json",
                manager.cluster_timeline_processes(),
            )
            write_metrics_snapshot(
                results_directory / f"{prefix}_metrics.json",
                manager.metrics,
                extra={
                    **manager.cluster_view(),
                    "history": manager.history.summary_dict(),
                },
            )

        for step in (
            _export_obs_artifacts,
            # Snapshot the run's learned cost model so --resume (or a
            # plain re-run of the same job) starts with warm predictors
            # instead of re-learning worker speeds from scratch. Failure
            # paths keep it too — exactly what a resume restores.
            lambda: save_cost_model(
                job, results_directory, manager.cost_service.model
            ),
        ):
            try:
                step()
            except Exception as e:  # noqa: BLE001 - obs must not mask the run error
                print(
                    f"warning: obs artifact export failed: {e}",
                    file=sys.stderr,
                )

    await asyncio.to_thread(
        save_raw_traces,
        start_time, job, results_directory, master_trace, worker_traces,
    )
    performance = parse_worker_traces(worker_traces)
    await asyncio.to_thread(
        save_processed_results,
        start_time,
        job,
        results_directory,
        performance,
        scheduler_stats={
            "auction_greedy_fallbacks": assignment_ops.greedy_fallback_count(),
        },
    )
    print_results(master_trace, performance)
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    initialize_console_and_file_logging(args.log_file_path)
    # The master never opens the TPU: a chip belongs to one process, and
    # the chips are the workers'. The tpu-batch auction (<= 128x128)
    # solves on the host CPU.
    from tpu_render_cluster.utils.accelerator import (
        configure_compile_cache,
        pin_jax_to_host_cpu,
    )

    pin_jax_to_host_cpu()
    configure_compile_cache()
    if args.command == "run-job":
        return asyncio.run(run_job_command(args))
    if args.command == "serve":
        return asyncio.run(serve_command(args))
    return 2


if __name__ == "__main__":
    sys.exit(main())
