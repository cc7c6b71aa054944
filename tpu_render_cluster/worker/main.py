"""Worker CLI entry point.

Flag surface matches the reference's clap parser (reference:
worker/src/cli.rs:5-45): ``worker --masterServerHost H --masterServerPort P
--baseDirectory D --blenderBinary B [-p prependArgs] [-a appendArgs]
[--logFilePath F]`` — plus the new ``--backend`` selector
(``blender`` | ``tpu-raytrace`` | ``mock``).
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import sys
from pathlib import Path

from tpu_render_cluster.obs import (
    export_chrome_trace,
    write_metrics_snapshot,
)
from tpu_render_cluster.obs.startup import get_startup
from tpu_render_cluster.protocol import messages as pm
from tpu_render_cluster.utils.logging import initialize_console_and_file_logging
from tpu_render_cluster.worker.backends import create_backend
from tpu_render_cluster.worker.runtime import Worker


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="trc-worker", description="Render cluster worker")
    parser.add_argument("--masterServerHost", dest="master_host", required=True)
    parser.add_argument("--masterServerPort", dest="master_port", type=int, required=True)
    parser.add_argument("--baseDirectory", dest="base_directory", required=True)
    parser.add_argument("--blenderBinary", dest="blender_binary", default="blender")
    parser.add_argument("-p", "--blenderPrependArguments", dest="prepend_arguments", default=None)
    parser.add_argument("-a", "--blenderAppendArguments", dest="append_arguments", default=None)
    parser.add_argument("--logFilePath", dest="log_file_path", default=None)
    parser.add_argument(
        "--backend",
        choices=["blender", "tpu-raytrace", "mock"],
        default="blender",
        help="Render backend (default: blender, matching the reference).",
    )
    parser.add_argument(
        "--sharding",
        choices=["none", "tile", "spp"],
        default="none",
        help="tpu-raytrace only: split each frame across the local device "
        "mesh (tile = horizontal bands, spp = sample subsets psum-averaged "
        "over ICI; tpu_render_cluster/parallel/sharded_render.py).",
    )
    parser.add_argument(
        "--coordinatorAddress",
        dest="coordinator_address",
        default=None,
        help="tpu-raytrace only: join a multi-host JAX distributed runtime "
        "at this coordinator (host:port); with --numProcesses/--processId "
        "the worker's device mesh then spans hosts (DCN) as well as its "
        "local slice (ICI). Env fallbacks: JAX_COORDINATOR_ADDRESS / "
        "JAX_NUM_PROCESSES / JAX_PROCESS_ID.",
    )
    parser.add_argument(
        "--numProcesses", dest="num_processes", type=int, default=None,
    )
    parser.add_argument(
        "--processId", dest="process_id", type=int, default=None,
    )
    parser.add_argument(
        "--renderSize",
        dest="render_size",
        default="512x512",
        help="tpu-raytrace only: output WxH (default 512x512).",
    )
    parser.add_argument(
        "--renderSamples",
        dest="render_samples",
        type=int,
        default=8,
        help="tpu-raytrace only: samples per pixel (default 8).",
    )
    parser.add_argument(
        "--telemetryPort",
        dest="telemetry_port",
        type=int,
        default=None,
        help="Serve this worker's live metrics over HTTP on this port: "
        "/metrics (Prometheus text exposition) + /healthz. 0 picks an "
        "ephemeral port. Defaults to the TRC_OBS_WORKER_PORT environment "
        "variable; omit both to disable.",
    )
    parser.add_argument(
        "--telemetryHost",
        dest="telemetry_host",
        default="0.0.0.0",
        help="Bind address for the telemetry endpoints (default 0.0.0.0 so "
        "a remote Prometheus/dashboard can scrape the worker, matching "
        "the master's posture; use 127.0.0.1 to keep them local).",
    )
    parser.add_argument(
        "--router",
        default=None,
        help="host:port of the shard router's control endpoint. When set, "
        "a lost master does not end this worker: it asks the router's "
        "route_worker op for the least-loaded live shard and re-homes "
        "there (requires the router to be started with --shardWorkers). "
        "Master-requested migrations (rebalancing) are also followed.",
    )
    parser.add_argument(
        "--warmScene",
        dest="warm_scene",
        default=None,
        help="tpu-raytrace only: prepare this scene at the worker's own "
        "shape BEFORE connecting to the master, so the job window never "
        "contains XLA compilation (the analog of pre-pulling the Blender "
        "image). Fills the start-up stages geometry, program_build and "
        "first_execute (worker_startup_stage_seconds). Without it a worker "
        "prepares each job when a scheduler service announces it (the same "
        "call, credited to the same stages while the first frame is "
        "awaited); under a master that announces no job the cost lies in "
        "first_frame.",
    )
    return parser


def make_backend(args: argparse.Namespace):
    if args.backend == "blender":
        return create_backend(
            "blender",
            blender_binary=args.blender_binary,
            base_directory=args.base_directory,
            prepend_arguments=args.prepend_arguments,
            append_arguments=args.append_arguments,
        )
    if args.backend == "tpu-raytrace":
        with get_startup().child("import_jax"):
            import jax  # noqa: F401 - timed here, used by what follows

        from tpu_render_cluster.parallel.mesh import initialize_multihost
        from tpu_render_cluster.utils.accelerator import configure_compile_cache

        configure_compile_cache()
        # Must happen before any other JAX use: afterwards jax.devices()
        # is the global (cross-host) set and sharded rendering spans DCN.
        initialize_multihost(
            args.coordinator_address, args.num_processes, args.process_id
        )
        try:
            width, height = (int(v) for v in args.render_size.lower().split("x"))
        except ValueError as e:
            raise SystemExit(f"--renderSize must be WxH: {e}")
        return create_backend(
            "tpu-raytrace",
            base_directory=args.base_directory,
            width=width,
            height=height,
            samples=args.render_samples,
            sharding=None if args.sharding == "none" else args.sharding,
        )
    return create_backend("mock")


ROUTE_ATTEMPTS = 10
ROUTE_RETRY_SECONDS = 0.25


def make_router_route_fn(router: str):
    """``route_fn`` for ``Worker.connect_and_serve``: ask the shard
    router where to (re)connect. A worker loses its master at exactly the
    moment the control plane is most likely to be churning (a shard died,
    maybe the router is restarting too), so the lookup retries for a few
    seconds before giving up; None (exit) only when the router stays
    unreachable or has no live shard to offer for the whole window."""
    host, _, port_text = router.rpartition(":")
    if not host:
        raise SystemExit(f"--router must be host:port, got {router!r}")
    port = int(port_text)

    async def route_fn() -> tuple[str, int] | None:
        from tpu_render_cluster.sched.control import control_request

        for attempt in range(ROUTE_ATTEMPTS):
            try:
                response = await control_request(
                    host, port, {"op": "route_worker"}, timeout=10.0
                )
            except (OSError, ValueError, ConnectionError, asyncio.TimeoutError):
                response = None
            if response is not None and response.get("ok"):
                return str(response["host"]), int(response["port"])
            if attempt + 1 < ROUTE_ATTEMPTS:
                await asyncio.sleep(ROUTE_RETRY_SECONDS)
        return None

    return route_fn


async def _run_worker(
    worker: Worker,
    telemetry_port: int | None = None,
    telemetry_host: str = "0.0.0.0",
    router: str | None = None,
):
    """Run to completion with SIGTERM wired to a graceful drain.

    A terminated worker daemon (node maintenance, preemption) finishes
    the frame it is rendering, returns its queue to the master via the
    goodbye message, and exits cleanly — instead of vanishing and making
    the master pay a heartbeat-timeout eviction to rediscover the frames.

    With ``telemetry_port`` set, the worker-local telemetry endpoints
    (/metrics + /healthz; obs/http.py) serve this daemon's registry live
    — the pull-based counterpart of the compact heartbeat piggyback the
    master aggregates.
    """
    loop = asyncio.get_running_loop()
    try:
        loop.add_signal_handler(signal.SIGTERM, worker.request_drain)
    except (NotImplementedError, RuntimeError):  # non-Unix loop
        pass
    telemetry = None
    history_sampler = None
    if telemetry_port is not None:
        from tpu_render_cluster.obs import HistorySampler, HistoryStore
        from tpu_render_cluster.obs.http import TelemetryServer

        # The worker's own metrics-history ring (obs/history.py): the
        # /history endpoint answers range/rate/quantile-over-window
        # queries so an operator (or the federated router) can see the
        # moments leading up to an incident on THIS daemon, not just the
        # cumulative /metrics snapshot.
        history = HistoryStore(worker.metrics)
        history_sampler = HistorySampler(history)
        history_sampler.start()
        telemetry = TelemetryServer(
            worker.metrics,
            host=telemetry_host,
            port=telemetry_port,
            healthz_fn=lambda: {
                "role": "worker",
                "worker_id": pm.worker_id_to_string(worker.worker_id),
                "backend": type(worker.backend).__name__,
            },
            history=history,
        )
        await telemetry.start()
    try:
        if router is not None:
            return await worker.connect_and_serve(make_router_route_fn(router))
        return await worker.connect_and_run_to_job_completion()
    finally:
        if telemetry is not None:
            await telemetry.stop()
        if history_sampler is not None:
            await history_sampler.stop()
        try:
            loop.remove_signal_handler(signal.SIGTERM)
        except (NotImplementedError, RuntimeError, ValueError):
            pass


def main(argv: list[str] | None = None) -> int:
    startup = get_startup()
    startup.enter("backend_init")
    args = build_parser().parse_args(argv)
    initialize_console_and_file_logging(args.log_file_path)
    backend = make_backend(args)
    if args.warm_scene and args.backend == "tpu-raytrace":
        backend.warm(args.warm_scene)
    startup.enter("connect")
    # The worker's tracer begins here: what start-up has recorded so far
    # is handed over to it (Worker.__init__), the rest is written through.
    worker = Worker(args.master_host, args.master_port, backend)
    # Which timeline is which chip's: the device stamp's index rides the
    # exported timeline's process metadata as well as the snapshot.
    device = getattr(backend, "device", None)
    if device:
        worker.span_tracer.process_labels = {
            key: device[key] for key in ("platform", "device_id", "chip")
        }
    from tpu_render_cluster.obs.http import resolve_telemetry_port

    telemetry_port = resolve_telemetry_port(
        args.telemetry_port, "TRC_OBS_WORKER_PORT"
    )
    try:
        asyncio.run(
            _run_worker(worker, telemetry_port, args.telemetry_host, args.router)
        )
    finally:
        # Export this daemon's obs artifacts even when the run died (the
        # partial timeline matters most in exactly those runs): in
        # distributed mode the master only holds the compact heartbeat
        # payloads, so the worker's full span timeline (connect + per-frame
        # queue_wait/read/render/write) and registry live here. Filenames
        # match the master's artifact globs so analysis/run_all pointed at
        # (or above) this directory loads them.
        obs_directory = Path(args.base_directory) / "obs"
        worker_name = f"worker-{pm.worker_id_to_string(worker.worker_id)}"
        try:
            export_chrome_trace(
                obs_directory / f"{worker_name}_trace-events.json",
                [worker.span_tracer],
            )
            extra = {}
            # Which device rendered (tpu-raytrace only): platform, kind and
            # index, stamped once when the backend was built.
            if device:
                extra["device"] = device
            # Which trace kernel each program it built holds (tpu-raytrace).
            kernels = getattr(backend, "trace_kernels", None)
            if kernels:
                extra["trace_kernels"] = dict(kernels)
            write_metrics_snapshot(
                obs_directory / f"{worker_name}_metrics.json",
                worker.metrics,
                extra=extra,
            )
        except Exception as e:  # noqa: BLE001 - obs must not mask the run error
            print(f"warning: obs artifact export failed: {e}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
