"""The worker as the benchmark starts it: `tpu_render_cluster.worker.main`
unchanged, plus the two things only the process that holds the chip can
give — a profiler trace of a slice of the window, and the device's peak
memory.

    python benchmark/lib/worker_entry.py --bench-index I --bench-dir D [worker arguments]

With `BENCH_TRACE=1` a watcher thread waits for `D/trace-<I>.go` (its text
is the slice's length in seconds), traces that long into `D/trace-<I>/`,
and writes `D/trace-<I>.done` with the slice's wall-clock edges. JAX is
imported inside that thread only, after the worker has opened its device.
When the worker's `main` returns (graceful drain on SIGTERM, or the job's
end), the device's memory statistics go to `D/device-<I>.json`, and then
the watcher is waited for: a worker that leaves while its profile is being
written writes it out first.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

# Run as a script, sys.path[0] is benchmark/lib: the package is two up.
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

CLOCK_MARK = "bench_clock_mark"


def _trace_slice(directory: Path, index: int, stop: threading.Event) -> None:
    go = directory / f"trace-{index}.go"
    while not go.exists():
        if stop.wait(0.05):
            return
    seconds = float(go.read_text().strip() or 5)
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # Python frames would swamp the trace
    options.host_tracer_level = 2
    report = {"index": index, "seconds": seconds}
    try:
        jax.profiler.start_trace(str(directory / f"trace-{index}"), profiler_options=options)
        # One host event at a known wall-clock time ties the trace's clock
        # to the clock the worker's own spans use.
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            report["mark_wall_s"] = time.time()
        report["start_wall_s"] = time.time()
        stop.wait(seconds)
        report["stop_wall_s"] = time.time()
        jax.profiler.stop_trace()
        report["written_wall_s"] = time.time()
    except Exception as error:  # noqa: BLE001 - reported to the harness, which fails the run
        report["error"] = repr(error)
    (directory / f"trace-{index}.done").write_text(json.dumps(report))


def _write_device_stats(directory: Path, index: int) -> None:
    import jax

    device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    (directory / f"device-{index}.json").write_text(json.dumps({
        "platform": device.platform,
        "kind": device.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    }))


def main(argv: list[str]) -> int:
    if argv[:1] != ["--bench-index"] or argv[2:3] != ["--bench-dir"]:
        raise SystemExit(__doc__)
    index, directory, worker_argv = int(argv[1]), Path(argv[3]), argv[4:]
    from tpu_render_cluster.worker.main import main as worker_main

    stop = threading.Event()
    watcher = None
    if os.environ.get("BENCH_TRACE") == "1":
        watcher = threading.Thread(
            target=_trace_slice, args=(directory, index, stop), daemon=True
        )
        watcher.start()
    try:
        return worker_main(worker_argv)
    finally:
        stop.set()
        # The device's numbers first: the harness takes `trace-<I>.done` as
        # this worker's last word, and a worker that leaves with its job
        # (no SIGTERM) is still writing its profile here.
        _write_device_stats(directory, index)
        if watcher is not None:
            watcher.join(timeout=60)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
