"""JSON-lines control plane for the multi-job scheduler.

One request per line, one response per line, over a plain TCP socket —
deliberately NOT the worker WebSocket protocol, so the reference-shaped
worker wire surface stays untouched and a shell script can drive the
scheduler with ``nc``. Operations:

- ``{"op": "submit", "spec": {"job": {...BlenderJob...}, "weight": 3, "priority": 0}}``
  -> ``{"ok": true, "job_id": "job-0001"}``
- ``{"op": "status"}`` -> ``{"ok": true, "sched": {...scheduler_view...}}``
  — its ``jobs`` lists every queued and running job and the newest 256
  ended ones (``sched/manager.py::ENDED_JOBS_LISTED``), not every job the
  service ever had
- ``{"op": "status", "job_id": "job-0001"}`` -> ``{"ok": true, "job": {...}}``
  — any job the service has had, ended ones from the view frozen as they ended
- ``{"op": "handbacks"}`` / ``{"op": "handbacks", "since": 1759250000.5}`` ->
  ``{"ok": true, "handbacks": [...]}`` — every unit of a listed job that left
  a worker without a result (preemption, steal, eviction, drain, error,
  failed dispatch), oldest first, with ``since`` those after that wall time:
  what the master can say of a (job, frame) that two workers rendered. A
  job's are dropped when it leaves the list of ended jobs
- ``{"op": "results", "job_id": "job-0001"}`` -> ``{"ok": true, "job_name": ...,
  "results": [{"frame": 7, "worker": "0a1b2c3d"}, ...]}`` — whose result
  finished each finished unit of the job: the master's record of what a
  worker delivered, for one that died and left none of its own
- ``{"op": "cancel", "job_id": "job-0001"}`` -> ``{"ok": true, "cancelled": bool}``
- ``{"op": "drain"}`` -> stop admitting; the service exits when idle
- ``{"op": "migrate_workers", "count": 2, "host": "...", "port": N}``
  -> ``{"ok": true, "migrating": n}`` — shed up to ``count`` workers
  toward another shard master (the router's rebalance move)
- ``{"op": "alerts"}`` -> ``{"ok": true, "alerts": [...], "slo": {...}}``
  — the SLO engine's structured alert log (obs/slo.py: one ``fire`` per
  breach episode, one ``clear`` per recovery) plus the live per-job
  attainment/burn view
- ``{"op": "ping"}`` -> liveness

Errors come back as ``{"ok": false, "error": "..."}``; the connection
survives them (a client can retry a fixed submission on the same socket).
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import TYPE_CHECKING, Any

from tpu_render_cluster.sched.models import JobSpec

if TYPE_CHECKING:
    from tpu_render_cluster.sched.manager import JobManager

logger = logging.getLogger(__name__)

MAX_LINE_BYTES = 16 * 1024 * 1024  # a job TOML payload is tiny; be generous


async def handle_request(manager: "JobManager", request: dict[str, Any]) -> dict[str, Any]:
    """Execute one control operation against the manager (pure dispatch —
    shared by the TCP server and in-process callers/tests)."""
    op = request.get("op")
    try:
        if op == "ping":
            return {"ok": True, "pong": True}
        if op == "submit":
            spec = JobSpec.from_dict(request.get("spec") or {})
            job_id = manager.submit(spec)
            return {"ok": True, "job_id": job_id}
        if op == "status":
            job_id = request.get("job_id")
            if job_id is None:
                return {"ok": True, "sched": manager.scheduler_view()}
            view = manager.job_status(str(job_id))
            if view is None:
                return {"ok": False, "error": f"unknown job_id: {job_id!r}"}
            return {"ok": True, "job": view}
        if op == "handbacks":
            return {"ok": True, "handbacks": manager.handbacks_view(request.get("since"))}
        if op == "results":
            job_id = str(request.get("job_id"))
            results = manager.results_view(job_id)
            if results is None:
                return {"ok": False, "error": f"unknown job_id: {job_id!r}"}
            return {"ok": True, **results}
        if op == "cancel":
            job_id = request.get("job_id")
            if job_id is None:
                return {"ok": False, "error": "cancel requires job_id"}
            cancelled = await manager.cancel_job(str(job_id))
            return {"ok": True, "cancelled": cancelled}
        if op == "drain":
            manager.request_drain()
            return {"ok": True, "draining": True}
        if op == "migrate_workers":
            host = request.get("host")
            port = request.get("port")
            if not host or port is None:
                return {"ok": False, "error": "migrate_workers requires host and port"}
            moved = await manager.migrate_workers(
                int(request.get("count", 1)),
                str(host),
                int(port),
                reason=request.get("reason"),
            )
            return {"ok": True, "migrating": moved}
        if op == "alerts":
            return {
                "ok": True,
                "alerts": manager.slo.alerts_view(),
                "slo": manager.slo.view(),
            }
        return {"ok": False, "error": f"unknown op: {op!r}"}
    except (ValueError, RuntimeError, KeyError, TypeError) as e:
        return {"ok": False, "error": str(e)}


class ControlServer:
    """The TCP JSON-lines frontend over ``handle_request``."""

    def __init__(
        self, manager: "JobManager", host: str = "127.0.0.1", port: int = 0
    ) -> None:
        self.manager = manager
        self.host = host
        self.port = port
        self._server: asyncio.Server | None = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._on_connection, self.host, self.port, limit=MAX_LINE_BYTES
        )
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info("Scheduler control listening on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            try:
                await asyncio.wait_for(self._server.wait_closed(), 5.0)
            except asyncio.TimeoutError:
                logger.warning("Control server close timed out.")

    async def _on_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        peer = writer.get_extra_info("peername")
        try:
            while True:
                line = await reader.readline()
                if not line:
                    return
                try:
                    request = json.loads(line)
                    if not isinstance(request, dict):
                        raise ValueError("request must be a JSON object")
                except (json.JSONDecodeError, ValueError) as e:
                    response: dict[str, Any] = {"ok": False, "error": f"bad request: {e}"}
                else:
                    response = await handle_request(self.manager, request)
                writer.write(json.dumps(response).encode("utf-8") + b"\n")
                await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass  # client went away mid-request; nothing to answer
        except Exception as e:  # noqa: BLE001 - one bad client must not kill the plane
            logger.warning("Control connection from %s failed: %s", peer, e)
        finally:
            writer.close()


async def control_request(
    host: str, port: int, request: dict[str, Any], *, timeout: float = 30.0
) -> dict[str, Any]:
    """One-shot client: connect, send one request line, read the answer."""
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection(host, port, limit=MAX_LINE_BYTES), timeout
    )
    try:
        writer.write(json.dumps(request).encode("utf-8") + b"\n")
        await writer.drain()
        line = await asyncio.wait_for(reader.readline(), timeout)
        if not line:
            raise ConnectionError("control server closed the connection")
        response = json.loads(line)
        if not isinstance(response, dict):
            raise ValueError("control response must be a JSON object")
        return response
    finally:
        writer.close()


def control_request_sync(
    host: str, port: int, request: dict[str, Any], *, timeout: float = 30.0
) -> dict[str, Any]:
    return asyncio.run(control_request(host, port, request, timeout=timeout))
