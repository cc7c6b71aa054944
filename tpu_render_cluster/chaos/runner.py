"""The chaos harness: run a real in-process cluster under a fault plan.

Wraps ``harness/local.py`` — the full production stack (accepting server,
3-step handshake, heartbeats, real distribution strategies, real
WebSockets on localhost) — with the plan's fault executors wired into the
three seams: ``FaultyConnection`` under each worker's reconnecting client,
``FaultyBackend`` around each mock renderer, and the dispatch-delay shim
inside the master's worker handles. After the job completes (and it MUST
complete — that is invariant #1) the run is audited by
``chaos/invariants.py`` and its obs artifacts are exported like any other
run's, so the merged cluster timeline of a faulted job can be validated
and eyeballed in Perfetto.

Timeout compression: production heartbeat/backoff budgets (10 s pings,
60 s pong windows) would stretch each scenario to minutes, so the run
executes under the plan's ``ChaosTimings`` via the same ``TRC_*``
overrides a deployment would use, restored afterwards.

CLI::

    python -m tpu_render_cluster.chaos.runner --seed 7 --workers 3 \
        [--frames 24] [--plan plan.toml] [--results-directory DIR]

exits non-zero if any invariant is violated, and prints the report (plan
fingerprint, injected faults, the master's exactly-once ledger) as JSON.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from tpu_render_cluster.chaos.inject import MasterChaosHooks, WorkerChaosController
from tpu_render_cluster.chaos.invariants import (
    check_invariants,
    check_multi_job_invariants,
    counter_total,
    ledger_stats,
)
from tpu_render_cluster.chaos.plan import FaultPlan
from tpu_render_cluster.harness import local as local_harness
from tpu_render_cluster.jobs.models import (
    BlenderJob,
    DistributionStrategy,
    DynamicStrategyOptions,
)
from tpu_render_cluster.master.cluster import ClusterManager
from tpu_render_cluster.obs import MetricsRegistry
from tpu_render_cluster.worker.backends.chaos import FaultyBackend
from tpu_render_cluster.worker.backends.mock import MockBackend
from tpu_render_cluster.worker.runtime import Worker

DEFAULT_FRAMES = 24
DEFAULT_RENDER_SECONDS = 0.12


def unit_latency_stats(unit_seconds: list[float]) -> dict[str, float]:
    """Exact percentiles over the master's per-unit winning-result
    latencies (state.unit_seconds) — the tail the predictive scheduler
    is judged on."""
    if not unit_seconds:
        return {"count": 0}
    ordered = sorted(unit_seconds)

    def pct(q: float) -> float:
        index = min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))
        return ordered[index]

    return {
        "count": len(ordered),
        "p50_s": pct(0.50),
        "p90_s": pct(0.90),
        "p99_s": pct(0.99),
        "max_s": ordered[-1],
    }


@dataclass
class ChaosReport:
    """Everything a chaos run produced: schedule, audit, ledger."""

    plan: FaultPlan
    violations: list[str]
    stats: dict[str, Any]
    artifacts: dict[str, str] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict[str, Any]:
        return {
            "plan": self.plan.to_dict(),
            "fingerprint": self.plan.fingerprint(),
            "ok": self.ok,
            "violations": self.violations,
            "stats": self.stats,
            "artifacts": self.artifacts,
        }


def _make_job(
    plan: FaultPlan, frames: int, strategy, tile_grid=None, slo=None
) -> BlenderJob:
    if strategy is None:
        # Dynamic (work-stealing) by default: the strategy with the most
        # fault-sensitive moving parts — steals race evictions, queue
        # mirrors drive victim selection.
        strategy = DistributionStrategy.dynamic_strategy(
            DynamicStrategyOptions(
                target_queue_size=3,
                min_queue_size_to_steal=1,
                min_seconds_before_resteal_to_elsewhere=1,
                min_seconds_before_resteal_to_original_worker=2,
            )
        )
    return BlenderJob(
        job_name=f"chaos-seed-{plan.seed}",
        job_description=f"chaos run (plan {plan.fingerprint()})",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=plan.workers,
        frame_distribution_strategy=strategy,
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
        tile_grid=tile_grid,
        slo=slo,
    )


@contextmanager
def _timing_overrides(timings):
    """Apply the plan's compressed timeout profile; restore on exit.

    Uses exactly the tuning surface a deployment has: the ``TRC_*``
    environment overrides plus the two heartbeat module constants and the
    master's reconnect-wait class attribute.
    """
    from tpu_render_cluster.master import worker_handle as wh
    from tpu_render_cluster.transport.reconnect import (
        ReconnectableServerConnection,
    )

    env = {
        "TRC_BACKOFF_BASE": str(timings.backoff_base),
        "TRC_BACKOFF_CAP_SECONDS": str(timings.backoff_cap_seconds),
        "TRC_MAX_CONNECT_RETRIES": str(timings.max_connect_retries),
        "TRC_MAX_RECONNECTS_PER_OP": str(timings.max_reconnects_per_op),
        "TRC_OP_DEADLINE_SECONDS": str(timings.op_deadline_seconds),
        "TRC_SEND_DEADLINE_SECONDS": str(timings.send_deadline_seconds),
        "TRC_RPC_DEADLINE_SECONDS": str(timings.rpc_deadline_seconds),
        "TRC_HEARTBEAT_PONG_RETRIES": str(timings.heartbeat_pong_retries),
    }
    saved_env = {name: os.environ.get(name) for name in env}
    saved_interval = wh.HEARTBEAT_INTERVAL_SECONDS
    saved_timeout = wh.HEARTBEAT_RESPONSE_TIMEOUT
    saved_wait = ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT
    os.environ.update(env)
    wh.HEARTBEAT_INTERVAL_SECONDS = timings.heartbeat_interval
    wh.HEARTBEAT_RESPONSE_TIMEOUT = timings.heartbeat_response_timeout
    ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT = (
        timings.max_wait_for_reconnect
    )
    try:
        yield
    finally:
        for name, value in saved_env.items():
            if value is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = value
        wh.HEARTBEAT_INTERVAL_SECONDS = saved_interval
        wh.HEARTBEAT_RESPONSE_TIMEOUT = saved_timeout
        ReconnectableServerConnection.MAX_WAIT_FOR_RECONNECT = saved_wait


async def _chaos_run(
    job: BlenderJob,
    backends: list[FaultyBackend],
    controllers: list[WorkerChaosController],
    hooks: MasterChaosHooks,
    registries: list[MetricsRegistry],
    master_registry: MetricsRegistry,
    flight_directory: str | Path | None = None,
):
    watchdogs: list[asyncio.Task] = []

    async def on_cluster_started(manager, workers, worker_tasks) -> None:
        for slot, worker in enumerate(workers):
            hooks.map_worker(worker.worker_id, slot)
            controllers[slot].attach(worker, worker_tasks[slot].cancel)
            watchdogs.append(
                asyncio.create_task(
                    controllers[slot].run_timed_faults(),
                    name=f"chaos-watchdog-{slot}",
                )
            )

    try:
        return await local_harness._run(
            job,
            backends,
            manager_factory=lambda job: ClusterManager(
                "127.0.0.1",
                0,
                job,
                metrics=master_registry,
                dispatch_delay_fn=hooks.dispatch_delay,
                flight_directory=flight_directory,
            ),
            worker_factory=lambda slot, port, backend: Worker(
                "127.0.0.1",
                port,
                backend,
                metrics=registries[slot],
                connection_wrapper=controllers[slot].wrap_connection,
            ),
            on_cluster_started=on_cluster_started,
            # Killed/hung workers never exit on their own (the master
            # skips dead workers at trace collection); reap them.
            worker_grace=3.0,
            allow_worker_failures=True,
        )
    finally:
        for watchdog in watchdogs:
            watchdog.cancel()
        await asyncio.gather(*watchdogs, return_exceptions=True)


def _aggregate_fault_counts(
    registries: list[MetricsRegistry], master_registry: MetricsRegistry
) -> dict[str, float]:
    from tpu_render_cluster.analysis.obs_events import (
        accumulate_chaos_fault_counts,
    )

    out: dict[str, float] = {}
    for registry in [*registries, master_registry]:
        accumulate_chaos_fault_counts(registry.snapshot(), out)
    return out


def run_chaos_job(
    plan: FaultPlan,
    *,
    frames: int = DEFAULT_FRAMES,
    strategy=None,
    results_directory: str | Path | None = None,
    render_seconds: float = DEFAULT_RENDER_SECONDS,
    timeout: float = 180.0,
    tile_grid: tuple[int, int] | None = None,
    slo=None,
    flight_directory: str | Path | None = None,
) -> ChaosReport:
    """Run one seeded chaos job end to end and audit the invariants.

    ``tile_grid`` torments the TILED pipeline: every frame splits into
    grid tiles, so the same fault schedule now races evictions, steals,
    duplicates, and drains against sub-frame units and the master's
    per-frame assembly ledger — audited at tile granularity
    (``invariants.check_tile_invariants``).

    ``slo`` (a ``jobs.models.JobSlo``) declares objectives on the chaos
    job so seeded fault schedules can drive the SLO engine into breach;
    the report's ``stats["slo"]`` then carries the final per-job
    attainment/burn view and the alert edge ledger.

    ``flight_directory`` arms the master's flight recorder with a dump
    target: incident triggers (an SLO fire, an eviction, a job failure)
    emit ``*_blackbox.json`` bundles there, and the report's
    ``stats["flight"]`` carries the trigger/dump ledger either way.
    """
    job = _make_job(plan, frames, strategy, tile_grid, slo)
    registries = [MetricsRegistry() for _ in range(plan.workers)]
    controllers = [
        WorkerChaosController(slot, plan.events_for(slot), registry=registries[slot])
        for slot in range(plan.workers)
    ]
    master_registry = MetricsRegistry()
    hooks = MasterChaosHooks(plan, registry=master_registry)
    backends = [
        FaultyBackend(
            MockBackend(
                load_seconds=0.004,
                save_seconds=0.004,
                render_seconds=render_seconds,
            ),
            controllers[slot],
        )
        for slot in range(plan.workers)
    ]
    started = time.time()
    with _timing_overrides(plan.timings):
        master_trace, worker_traces, manager, workers = asyncio.run(
            asyncio.wait_for(
                _chaos_run(
                    job,
                    backends,
                    controllers,
                    hooks,
                    registries,
                    master_registry,
                    flight_directory,
                ),
                timeout,
            )
        )

    artifacts: dict[str, str] = {}
    cluster_trace_document = None
    if results_directory is not None:
        results_directory = Path(results_directory)
        results_directory.mkdir(parents=True, exist_ok=True)
        prefix = results_directory / f"chaos-{plan.seed}-{plan.fingerprint()}"
        trace_path, metrics_path, cluster_trace_path = (
            local_harness.save_obs_artifacts(prefix, manager, workers)
        )
        artifacts = {
            "trace_events": str(trace_path),
            "metrics": str(metrics_path),
            "cluster_trace": str(cluster_trace_path),
        }
        cluster_trace_document = json.loads(
            Path(cluster_trace_path).read_text(encoding="utf-8")
        )
    else:
        # No directory given: still validate the merged timeline by
        # building the document in memory from the same collection path.
        from tpu_render_cluster.obs import merge_timeline

        cluster_trace_document = merge_timeline(
            manager.cluster_timeline_processes()
        )

    violations = check_invariants(
        manager, plan, cluster_trace_document=cluster_trace_document
    )
    master_snapshot = manager.metrics.snapshot()
    stats: dict[str, Any] = {
        "frames_total": len(manager.state.frames),
        "tiles_per_frame": job.tiles_per_frame(),
        "frames_assembled": manager.state.frames_assembled,
        "job_seconds": master_trace.job_finish_time - master_trace.job_start_time,
        "wall_seconds": time.time() - started,
        "worker_traces_collected": len(worker_traces),
        "faults_injected": _aggregate_fault_counts(registries, master_registry),
        "ledger": ledger_stats(master_snapshot),
        "reconnects": counter_total(
            master_snapshot, "master_worker_reconnects_total"
        ),
        "unit_latency": unit_latency_stats(manager.state.unit_seconds),
    }
    if manager.speculation.config.enabled or manager.speculation.launched_total:
        stats["speculation"] = manager.speculation.view()
    if manager.slo.tracked():
        stats["slo"] = manager.slo.view()
    if manager.flightrec.triggers or manager.flightrec.dumps:
        stats["flight"] = manager.flightrec.view()
    return ChaosReport(
        plan=plan, violations=violations, stats=stats, artifacts=artifacts
    )


async def _chaos_multi_run(
    specs,
    backends: list[FaultyBackend],
    controllers: list[WorkerChaosController],
    hooks: MasterChaosHooks,
    registries: list[MetricsRegistry],
    master_registry: MetricsRegistry,
):
    from tpu_render_cluster.sched.manager import JobManager, SchedulerConfig

    watchdogs: list[asyncio.Task] = []

    async def on_cluster_started(manager, workers, worker_tasks) -> None:
        for slot, worker in enumerate(workers):
            hooks.map_worker(worker.worker_id, slot)
            controllers[slot].attach(worker, worker_tasks[slot].cancel)
            watchdogs.append(
                asyncio.create_task(
                    controllers[slot].run_timed_faults(),
                    name=f"chaos-watchdog-{slot}",
                )
            )

    try:
        return await local_harness._run_multi_job(
            specs,
            backends,
            manager_factory=lambda: JobManager(
                "127.0.0.1",
                0,
                config=SchedulerConfig.from_env(),
                metrics=master_registry,
                dispatch_delay_fn=hooks.dispatch_delay,
            ),
            worker_factory=lambda slot, port, backend: Worker(
                "127.0.0.1",
                port,
                backend,
                metrics=registries[slot],
                connection_wrapper=controllers[slot].wrap_connection,
            ),
            on_cluster_started=on_cluster_started,
            worker_grace=3.0,
            allow_worker_failures=True,
        )
    finally:
        for watchdog in watchdogs:
            watchdog.cancel()
        await asyncio.gather(*watchdogs, return_exceptions=True)


def run_chaos_multi_job(
    plan: FaultPlan,
    *,
    jobs: int = 2,
    frames: int = DEFAULT_FRAMES,
    weights: list[float] | None = None,
    render_seconds: float = DEFAULT_RENDER_SECONDS,
    timeout: float = 240.0,
) -> ChaosReport:
    """Run CONCURRENT jobs through the scheduler under a seeded fault plan.

    The multi-job counterpart of ``run_chaos_job``: the same per-slot
    fault executors and compressed timeout profile, driving a
    ``sched.JobManager`` service instead of the single-job master, with
    ``jobs`` weighted submissions sharing the worker pool. The audit is
    ``check_multi_job_invariants`` — per-job completion + exactly-once
    ledgers + ghost sweeps, plus the plan's eviction/drain accounting.
    """
    from tpu_render_cluster.sched.models import JobSpec

    weights = weights or [float(2 ** i) for i in range(jobs)]
    if len(weights) != jobs:
        raise ValueError(f"need {jobs} weights, got {len(weights)}")
    specs = []
    for i in range(jobs):
        job = _make_job(plan, frames, None)
        job = BlenderJob.from_dict(
            {**job.to_dict(), "job_name": f"{job.job_name}-mj{i}"}
        )
        specs.append(JobSpec(job=job, weight=weights[i]))
    registries = [MetricsRegistry() for _ in range(plan.workers)]
    controllers = [
        WorkerChaosController(slot, plan.events_for(slot), registry=registries[slot])
        for slot in range(plan.workers)
    ]
    master_registry = MetricsRegistry()
    hooks = MasterChaosHooks(plan, registry=master_registry)
    backends = [
        FaultyBackend(
            MockBackend(
                load_seconds=0.004,
                save_seconds=0.004,
                render_seconds=render_seconds,
            ),
            controllers[slot],
        )
        for slot in range(plan.workers)
    ]
    started = time.time()
    with _timing_overrides(plan.timings):
        worker_traces, job_ids, manager, workers = asyncio.run(
            asyncio.wait_for(
                _chaos_multi_run(
                    specs, backends, controllers, hooks, registries,
                    master_registry,
                ),
                timeout,
            )
        )

    from tpu_render_cluster.obs import merge_timeline

    cluster_trace_document = merge_timeline(manager.cluster_timeline_processes())
    violations = check_multi_job_invariants(
        manager, plan, cluster_trace_document=cluster_trace_document
    )
    master_snapshot = manager.metrics.snapshot()
    stats: dict[str, Any] = {
        "jobs": {
            job_id: manager.job_status(job_id) for job_id in job_ids
        },
        "frames_total": frames * jobs,
        "wall_seconds": time.time() - started,
        "worker_traces_collected": len(worker_traces),
        "faults_injected": _aggregate_fault_counts(registries, master_registry),
        "ledger": ledger_stats(master_snapshot),
        "reconnects": counter_total(
            master_snapshot, "master_worker_reconnects_total"
        ),
    }
    return ChaosReport(plan=plan, violations=violations, stats=stats)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trc-chaos", description="Seeded fault-injection harness"
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--workers", type=int, default=3)
    parser.add_argument("--frames", type=int, default=DEFAULT_FRAMES)
    parser.add_argument(
        "--jobs",
        type=int,
        default=1,
        help="Run N weighted jobs CONCURRENTLY through the sched.JobManager "
        "service instead of one job on the single-job master (audited by "
        "the per-job invariants; obs artifacts are skipped in this mode).",
    )
    parser.add_argument(
        "--plan",
        default=None,
        help="TOML fault plan (overrides --seed/--workers; see chaos/plan.py)",
    )
    parser.add_argument(
        "--results-directory",
        default=None,
        help="Where to write the run's obs artifacts (default: results/chaos-runs)",
    )
    parser.add_argument("--timeout", type=float, default=180.0)
    parser.add_argument(
        "--tiles",
        default=None,
        help="Tile grid ROWSxCOLS (e.g. 2x2): torment the tile-sharded "
        "pipeline — sub-frame work units + the master's assembly ledger "
        "(single-job mode only).",
    )
    parser.add_argument(
        "--failover",
        action="store_true",
        help="Run the master-failover scenario (ha/chaos.py): a "
        "ledger-backed primary is killed mid-job, a standby replays the "
        "write-ahead ledger on the same port, re-adopts the workers via "
        "epoch-fenced re-announce, and the job completes — audited by the "
        "cross-incarnation exactly-once invariant. Uses "
        "FaultPlan.generate_failover(seed, workers) unless --plan is given.",
    )
    parser.add_argument(
        "--replicated-failover",
        dest="replicated_failover",
        action="store_true",
        help="Cross-host failover: the standby's ledger arrives by "
        "STREAMING REPLICATION only (no shared filesystem); the stream "
        "is partitioned and the follower lagged before the kill, then "
        "the router's PromotionMonitor promotes the replica, which "
        "finishes the job. Uses "
        "FaultPlan.generate_replicated_failover(seed, workers) unless "
        "--plan is given.",
    )
    parser.add_argument(
        "--shard-kill",
        dest="shard_kill",
        action="store_true",
        help="Two router-fronted shards, one killed whole (master AND "
        "control endpoint) mid-backlog: every orphaned worker must "
        "re-home through the router's route_worker op and the survivor "
        "finish all --jobs exactly once, with the router's fan-outs "
        "degrading the dead shard to absence. Uses "
        "FaultPlan.generate_shard_kill(seed, workers) unless --plan is "
        "given.",
    )
    return parser


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.replicated_failover:
        from tpu_render_cluster.ha.chaos import run_chaos_replicated_failover

        plan = (
            FaultPlan.from_toml(args.plan)
            if args.plan
            else FaultPlan.generate_replicated_failover(args.seed, args.workers)
        )
        report = run_chaos_replicated_failover(
            plan, frames=args.frames, timeout=args.timeout
        )
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1
    if args.shard_kill:
        from tpu_render_cluster.ha.chaos import run_chaos_shard_kill

        plan = (
            FaultPlan.from_toml(args.plan)
            if args.plan
            else FaultPlan.generate_shard_kill(args.seed, args.workers)
        )
        report = run_chaos_shard_kill(
            plan, jobs=args.jobs, frames=args.frames, timeout=args.timeout
        )
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1
    if args.failover:
        from tpu_render_cluster.ha.chaos import run_chaos_failover_job

        plan = (
            FaultPlan.from_toml(args.plan)
            if args.plan
            else FaultPlan.generate_failover(args.seed, args.workers)
        )
        results_directory = args.results_directory
        if results_directory is None:
            from tpu_render_cluster.analysis.paths import RESULTS_ROOT

            results_directory = RESULTS_ROOT / "chaos-runs"
        tile_grid = None
        if args.tiles:
            from tpu_render_cluster.jobs.tiles import parse_tile_grid

            tile_grid = parse_tile_grid(args.tiles)
        report = run_chaos_failover_job(
            plan,
            frames=args.frames,
            results_directory=results_directory,
            timeout=args.timeout,
            tile_grid=tile_grid,
        )
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1
    if args.plan:
        plan = FaultPlan.from_toml(args.plan)
    else:
        plan = FaultPlan.generate(args.seed, args.workers)
    if args.jobs > 1:
        report = run_chaos_multi_job(
            plan, jobs=args.jobs, frames=args.frames, timeout=args.timeout
        )
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1
    results_directory = args.results_directory
    if results_directory is None:
        from tpu_render_cluster.analysis.paths import RESULTS_ROOT

        results_directory = RESULTS_ROOT / "chaos-runs"
    tile_grid = None
    if args.tiles:
        from tpu_render_cluster.jobs.tiles import parse_tile_grid

        tile_grid = parse_tile_grid(args.tiles)
    report = run_chaos_job(
        plan,
        frames=args.frames,
        results_directory=results_directory,
        timeout=args.timeout,
        tile_grid=tile_grid,
        # Operator runs get blackbox bundles beside the other artifacts;
        # an incident-free run writes none.
        flight_directory=results_directory,
    )
    print(json.dumps(report.to_dict(), indent=2))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
