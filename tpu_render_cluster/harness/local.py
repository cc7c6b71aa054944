"""In-process cluster runner: one master + N workers over localhost.

Every run uses the full production stack — ClusterManager's accepting
server, the 3-step handshake, heartbeats, and the real distribution
strategies — only colocated in a single asyncio loop, exactly like the
integration tests. Traces are persisted with the same writer the master
CLI uses, so the output is indistinguishable from a multi-host run
(reference: master/src/main.rs:26-338 persistence path).
"""

from __future__ import annotations

import asyncio
import os
from datetime import datetime
from pathlib import Path

from tpu_render_cluster.jobs.models import BlenderJob
from tpu_render_cluster.master.cluster import ClusterManager
from tpu_render_cluster.master.persist import (
    parse_worker_traces,
    save_processed_results,
    save_raw_traces,
)
from tpu_render_cluster.obs import (
    MetricsRegistry,
    export_chrome_trace,
    export_cluster_trace,
    merge_wire,
    write_metrics_snapshot,
)
from tpu_render_cluster.protocol.messages import worker_id_to_string
from tpu_render_cluster.traces.master_trace import MasterTrace
from tpu_render_cluster.traces.worker_trace import WorkerTrace
from tpu_render_cluster.worker.backends.base import RenderBackend
from tpu_render_cluster.worker.runtime import Worker


async def _run(
    job: BlenderJob,
    backends: list[RenderBackend],
    *,
    manager_factory=None,
    worker_factory=None,
    on_cluster_started=None,
    worker_grace: float | None = None,
    allow_worker_failures: bool = False,
):
    """Run one in-process cluster job.

    The optional hooks are the chaos harness's seams (all default to the
    plain production path):

    - ``manager_factory(job)`` / ``worker_factory(slot, port, backend)``
      construct the components (e.g. with fault-injecting connection
      wrappers and per-slot registries);
    - ``on_cluster_started(manager, workers, worker_tasks)`` runs once the
      tasks exist — where fault watchdogs attach and start;
    - ``worker_grace`` bounds how long to wait for worker tasks after the
      master finishes; leftovers (crashed/hung workers that will never
      exit) are cancelled instead of hanging the harness;
    - ``allow_worker_failures`` tolerates worker tasks that died of
      injected faults; without it the first worker exception re-raises.
    """
    # A fresh registry per run: harness callers (tests, sweep scripts)
    # run many jobs in one process, and per-run artifacts must not
    # accumulate counts across runs the way the CLI's process-global
    # default (one job per process) is allowed to.
    if manager_factory is not None:
        manager = manager_factory(job)
    else:
        manager = ClusterManager("127.0.0.1", 0, job, metrics=MetricsRegistry())
    server_task = asyncio.create_task(manager.initialize_server_and_run_job())
    while manager._server is None:
        if server_task.done():
            # Startup failed (e.g. port bind); await to surface the real
            # exception instead of spinning until the outer timeout.
            await server_task
            raise RuntimeError("master server task exited before startup")
        await asyncio.sleep(0.01)
    # Fresh per-worker registries too: colocated workers must not share
    # the process-global registry or their heartbeat payloads (and the
    # per-worker snapshots in the metrics artifact) would double-count.
    if worker_factory is not None:
        workers = [
            worker_factory(slot, manager.port, backend)
            for slot, backend in enumerate(backends)
        ]
    else:
        workers = [
            Worker("127.0.0.1", manager.port, backend, metrics=MetricsRegistry())
            for backend in backends
        ]
    worker_tasks = [
        asyncio.create_task(w.connect_and_run_to_job_completion()) for w in workers
    ]
    if on_cluster_started is not None:
        await on_cluster_started(manager, workers, worker_tasks)
    master_trace, worker_traces = await server_task
    if allow_worker_failures and worker_grace is None:
        # Tolerating failures implies tolerating workers that never exit
        # (a hung/killed worker's task has no reason to finish): an
        # unbounded wait here would hang the harness, so failure-tolerant
        # runs always get a finite reap window.
        worker_grace = 60.0
    if worker_grace is None and not allow_worker_failures:
        await asyncio.gather(*worker_tasks)
    else:
        _done, pending = await asyncio.wait(
            worker_tasks, timeout=worker_grace
        )
        for task in pending:
            task.cancel()
        results = await asyncio.gather(*worker_tasks, return_exceptions=True)
        if not allow_worker_failures:
            for result in results:
                if isinstance(result, Exception):
                    raise result
    return master_trace, worker_traces, manager, workers


async def _run_multi_job(
    specs,
    backends: list[RenderBackend],
    *,
    manager_factory=None,
    worker_factory=None,
    on_cluster_started=None,
    driver=None,
    worker_grace: float | None = None,
    allow_worker_failures: bool = False,
):
    """Run the multi-job scheduler service over an in-process cluster.

    The service analog of ``_run``: one ``sched.JobManager`` accepting
    real localhost WebSockets, N workers, every ``JobSpec`` in ``specs``
    submitted up front, then a drain request — ``serve()`` returns once
    every job finished. The chaos seams match ``_run``'s
    (``manager_factory()`` / ``worker_factory(slot, port, backend)`` /
    ``on_cluster_started``); ``driver(manager, workers)`` additionally
    runs after submission so tests can exercise the lifecycle API
    (cancel mid-run, late submissions, status polls) against the live
    service before the drain lands.
    """
    from tpu_render_cluster.sched.manager import JobManager

    if manager_factory is not None:
        manager = manager_factory()
    else:
        manager = JobManager("127.0.0.1", 0, metrics=MetricsRegistry())
    serve_task = asyncio.create_task(manager.serve())
    while manager._server is None:
        if serve_task.done():
            await serve_task
            raise RuntimeError("scheduler serve task exited before startup")
        await asyncio.sleep(0.01)
    if worker_factory is not None:
        workers = [
            worker_factory(slot, manager.port, backend)
            for slot, backend in enumerate(backends)
        ]
    else:
        workers = [
            Worker("127.0.0.1", manager.port, backend, metrics=MetricsRegistry())
            for backend in backends
        ]
    worker_tasks = [
        asyncio.create_task(w.connect_and_run_to_job_completion()) for w in workers
    ]
    if on_cluster_started is not None:
        await on_cluster_started(manager, workers, worker_tasks)
    job_ids = [manager.submit(spec) for spec in specs]
    if driver is not None:
        await driver(manager, workers)
    manager.request_drain()
    worker_traces = await serve_task
    if allow_worker_failures and worker_grace is None:
        worker_grace = 60.0
    if worker_grace is None and not allow_worker_failures:
        await asyncio.gather(*worker_tasks)
    else:
        _done, pending = await asyncio.wait(worker_tasks, timeout=worker_grace)
        for task in pending:
            task.cancel()
        results = await asyncio.gather(*worker_tasks, return_exceptions=True)
        if not allow_worker_failures:
            for result in results:
                if isinstance(result, Exception):
                    raise result
    return worker_traces, job_ids, manager, workers


def run_local_multi_job(
    specs,
    backends: list[RenderBackend],
    *,
    timeout: float = 600.0,
    driver=None,
):
    """Run jobs through the scheduler service on an in-process cluster.

    Returns ``(worker_traces, job_ids, manager, workers)`` — the manager
    is handed back live (post-shutdown) so callers can audit per-job
    states, ledgers, and the scheduler view.
    """
    return asyncio.run(
        asyncio.wait_for(_run_multi_job(specs, backends, driver=driver), timeout)
    )


def _run_local_job_full(
    job: BlenderJob, backends: list[RenderBackend], timeout: float
) -> tuple[MasterTrace, list[tuple[str, WorkerTrace]], ClusterManager, list[Worker]]:
    return asyncio.run(asyncio.wait_for(_run(job, backends), timeout))


def run_local_job(
    job: BlenderJob,
    backends: list[RenderBackend],
    *,
    timeout: float = 600.0,
) -> tuple[MasterTrace, list[tuple[str, WorkerTrace]]]:
    """Run one job on an in-process cluster; returns (master trace, worker traces)."""
    master_trace, worker_traces, _, _ = _run_local_job_full(job, backends, timeout)
    return master_trace, worker_traces


def save_obs_artifacts(
    prefix_path: Path, manager: ClusterManager, workers: list[Worker]
) -> tuple[Path, Path, Path]:
    """Write ``<prefix>_trace-events.json`` + ``<prefix>_metrics.json``
    + ``<prefix>_cluster_trace-events.json``.

    The trace-event file merges the master's span tracer with every
    worker's (one Perfetto process row each) and loads directly in
    https://ui.perfetto.dev or chrome://tracing. The metrics file carries
    the master registry snapshot, the live cluster view, each worker's
    full registry snapshot, and their ``merge_wire`` aggregation —
    exactly what a multi-host master assembles from heartbeat payloads,
    but collected in-process after the run. The cluster trace is the
    CAUSAL timeline: the span events each worker piggybacked on its
    job-finished response, rebased onto the master clock by the heartbeat
    clock-offset estimates, pids deduplicated, with flow arrows linking
    every frame's assign span to its worker phases and result span.
    """
    from tpu_render_cluster.obs import get_registry

    trace_path = export_chrome_trace(
        prefix_path.with_name(prefix_path.name + "_trace-events.json"),
        [manager.span_tracer] + [w.span_tracer for w in workers],
    )
    # The merged causal timeline goes through the same collection path a
    # multi-host master uses (span events shipped on job-finished, offsets
    # from the heartbeat estimator) — in-process the offsets are near zero,
    # but the machinery is identical.
    cluster_trace_path = export_cluster_trace(
        prefix_path.with_name(prefix_path.name + "_cluster_trace-events.json"),
        manager.cluster_timeline_processes(),
        extra_other_data=manager.timeline_other_data(),
    )
    worker_snapshots = {
        worker_id_to_string(w.worker_id): w.metrics.snapshot() for w in workers
    }
    metrics_path = write_metrics_snapshot(
        prefix_path.with_name(prefix_path.name + "_metrics.json"),
        manager.metrics,
        extra={
            **manager.cluster_view(),
            "workers": worker_snapshots,
            "workers_wire_merged": merge_wire(
                [w.metrics.to_wire() for w in workers]
            ),
            # Harness workers run with fresh per-run registries, but the
            # RENDER path (the backend's tier counter and launch
            # occupancy series) reports into the process-global
            # registry — snapshot it too or those series never reach the
            # artifact. Process-scoped and CUMULATIVE across runs in one
            # harness process, so it is tagged with the pid: consumers
            # (analysis/obs_events.summarize_launch_occupancy) keep only
            # the newest snapshot per pid instead of summing every file's
            # copy of the same counters.
            "process_metrics": {
                "pid": os.getpid(),
                "metrics": get_registry().snapshot(),
            },
            # Continuous-observability roll-up (obs/history.py): per-
            # counter increase/rate/trend and per-gauge envelopes over the
            # run's sampled window — the statistics.json `history` fold.
            "history": manager.history.summary_dict(),
        },
    )
    return trace_path, metrics_path, cluster_trace_path


def run_and_persist(
    job: BlenderJob,
    backends: list[RenderBackend],
    results_directory: str | Path,
    *,
    timeout: float = 600.0,
) -> Path:
    """Run and write ``*_raw-trace.json`` + processed results; returns the raw path.

    Also emits the obs artifacts next to them: ``*_trace-events.json``
    (Chrome trace-event spans for master, workers, and transport),
    ``*_metrics.json`` (metrics snapshot incl. frame-phase histograms),
    and ``*_cluster_trace-events.json`` (the merged clock-corrected causal
    timeline with per-frame flow arrows).
    """
    from tpu_render_cluster.ops import assignment as assignment_ops

    start = datetime.now()
    assignment_ops.reset_greedy_fallback_count()
    master_trace, worker_traces, manager, workers = _run_local_job_full(
        job, backends, timeout
    )
    results_directory = Path(results_directory)
    raw_path = save_raw_traces(start, job, results_directory, master_trace, worker_traces)
    save_obs_artifacts(
        raw_path.with_name(raw_path.name.replace("_raw-trace.json", "")),
        manager,
        workers,
    )
    performance = parse_worker_traces(worker_traces)
    save_processed_results(
        start,
        job,
        results_directory,
        performance,
        scheduler_stats={
            "auction_greedy_fallbacks": assignment_ops.greedy_fallback_count(),
        },
    )
    return raw_path
