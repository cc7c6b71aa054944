"""Processes: the probe child, spawning in process groups, stopping on a
deadline. The pattern is `chip_smoke.py`'s, copied so that the yardstick
does not change when the program does.

Nothing here imports JAX: a process that has touched JAX holds the chip,
and the chips are the workers'.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

PROBE_SECONDS = 180


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero and print none."""


def chip_environment(index: int) -> dict[str, str]:
    """Confine a child to local chip `index`: the chip made visible and a
    1x1x1 grid, so each worker is its own one-chip slice (a copy of
    `tpu_render_cluster.utils.accelerator.chip_environment`)."""
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def free_port() -> int:
    with socket.socket() as probe_socket:
        probe_socket.bind(("127.0.0.1", 0))
        return probe_socket.getsockname()[1]


def probe(env: dict[str, str]) -> dict:
    """Ask a short-lived child what JAX sees; it has exited on return."""
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    try:
        result = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=env, timeout=PROBE_SECONDS,
        )
    except subprocess.TimeoutExpired:
        raise BenchFailure("probe: JAX did not come up in time") from None
    if result.returncode != 0:
        raise BenchFailure(f"probe: JAX failed to start:\n{result.stderr[-2000:]}")
    return json.loads(result.stdout.strip().splitlines()[-1])


class Processes:
    """The children of one run, each in its own process group."""

    def __init__(self) -> None:
        self._live: list[subprocess.Popen] = []

    def spawn(self, argv: list[str], log: Path, env: dict[str, str], cwd: Path) -> subprocess.Popen:
        log.parent.mkdir(parents=True, exist_ok=True)
        with open(log, "wb") as handle:
            process = subprocess.Popen(
                argv, stdout=handle, stderr=subprocess.STDOUT, env=env,
                cwd=cwd, start_new_session=True,
            )
        self._live.append(process)
        return process

    def check_alive(self, what: str) -> None:
        for process in self._live:
            code = process.poll()
            if code is not None:
                raise BenchFailure(f"{what}: {' '.join(process.args[1:4])} exited {code}")

    @staticmethod
    def terminate(processes: list[subprocess.Popen], seconds: float) -> list[int | None]:
        """SIGTERM, then wait until the deadline; returns the exit codes
        (None for a process that had to be left to `kill_all`)."""
        for process in processes:
            if process.poll() is None:
                process.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + seconds
        for process in processes:
            try:
                process.wait(max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
        return [process.poll() for process in processes]

    def kill_all(self) -> None:
        for process in self._live:
            try:
                os.killpg(process.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            process.wait()
        self._live.clear()
