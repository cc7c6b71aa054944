"""Sanitized integration runs of the C++ daemons (SURVEY.md §5.2).

The reference leans on Rust's type system for thread safety; the C++
daemons here are hand-threaded (acceptor + per-worker readers + heartbeat +
scheduling threads over shared worker maps), so every release must pass a
real cluster run under ThreadSanitizer and AddressSanitizer. A sanitizer
hit makes the daemon exit non-zero (``exitcode=66``) and prints a report,
failing these tests.

Runs are small (8 frames, 2 workers) to keep the ~5-20x sanitizer slowdown
inside CI budgets.
"""

from __future__ import annotations

import os
import shutil
import socket
import subprocess
import time
from pathlib import Path

import pytest

from tpu_render_cluster.native import build_master_daemon, build_worker_daemon

requires_gxx = pytest.mark.skipif(
    shutil.which("g++") is None, reason="g++ unavailable"
)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"

# The canonical marker every sanitizer workaround in the C++ sources must
# carry (grep-able, reason required on the same comment). The count is
# PINNED below: adding a workaround without updating the pin — and writing
# down why it is a false positive — fails the suite, so the suppression
# surface cannot grow silently.
_SUPPRESSION_MARKER = "trc-sanitizer-suppression:"
_EXPECTED_SUPPRESSIONS = 1  # trc_common.hpp cv_wait_for (uninstrumented
#                             pthread_cond_clockwait in older TSAN runtimes)


def test_sanitizer_suppression_count_is_pinned():
    """Source-scan audit (runs even without a toolchain): every sanitizer
    workaround is marked, reasoned, and counted."""
    markers: list[tuple[str, int, str]] = []
    for source in sorted(_NATIVE_DIR.glob("*.[ch]pp")):
        for lineno, line in enumerate(
            source.read_text().splitlines(), start=1
        ):
            if _SUPPRESSION_MARKER in line:
                reason = line.split(_SUPPRESSION_MARKER, 1)[1].strip()
                markers.append((source.name, lineno, reason))
    for name, lineno, reason in markers:
        assert reason, (
            f"{name}:{lineno}: sanitizer suppression without a reason — "
            f"write `// {_SUPPRESSION_MARKER} <why this is a false positive>`"
        )
    assert len(markers) == _EXPECTED_SUPPRESSIONS, (
        f"sanitizer suppression count changed: expected "
        f"{_EXPECTED_SUPPRESSIONS}, found {len(markers)}: {markers}. If the "
        "new workaround is justified, update _EXPECTED_SUPPRESSIONS in the "
        "same change — silent growth is exactly what this pin exists to stop."
    )

_SANITIZER_ENV = {
    "thread": {"TSAN_OPTIONS": "exitcode=66 halt_on_error=0"},
    "address": {"ASAN_OPTIONS": "exitcode=66 detect_leaks=0"},
}


def _sanitizer_works(sanitize: str) -> bool:
    """Probe the toolchain: some images lack the sanitizer runtimes."""
    probe = Path("/tmp") / f"trc-san-probe-{sanitize}"
    source = probe.with_suffix(".cpp")
    source.write_text("int main() { return 0; }\n")
    try:
        subprocess.run(
            ["g++", f"-fsanitize={sanitize}", "-o", str(probe), str(source)],
            check=True,
            capture_output=True,
            timeout=60,
        )
        return subprocess.run([str(probe)], timeout=30).returncode == 0
    except (subprocess.SubprocessError, OSError):
        return False


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _write_job(tmp_path: Path, workers: int, frames: int) -> Path:
    job_path = tmp_path / "job.toml"
    job_path.write_text(
        f'''
job_name = "sanitized-run"
job_description = "TSAN/ASAN integration job"
project_file_path = "%BASE%/p.blend"
render_script_path = "%BASE%/s.py"
frame_range_from = 1
frame_range_to = {frames}
wait_for_number_of_workers = {workers}
output_directory_path = "{tmp_path / 'frames'}"
output_file_name_format = "rendered-####"
output_file_format = "PNG"

[frame_distribution_strategy]
strategy_type = "dynamic"
target_queue_size = 3
min_queue_size_to_steal = 1
min_seconds_before_resteal_to_elsewhere = 1
min_seconds_before_resteal_to_original_worker = 2
'''
    )
    return job_path


@requires_gxx
@pytest.mark.usefixtures("kill_leftover_children")
@pytest.mark.parametrize("sanitize", ["thread", "address"])
def test_sanitized_cluster_run(tmp_path, sanitize):
    if not _sanitizer_works(sanitize):
        pytest.skip(f"-fsanitize={sanitize} runtime unavailable")
    master = build_master_daemon(sanitize=sanitize)
    worker = build_worker_daemon(sanitize=sanitize)
    assert master is not None, f"{sanitize}-sanitized master failed to build"
    assert worker is not None, f"{sanitize}-sanitized worker failed to build"

    env = {**os.environ, **_SANITIZER_ENV[sanitize]}
    port = _free_port()
    frames, workers = 8, 2
    job_path = _write_job(tmp_path, workers, frames)
    results = tmp_path / "results"
    master_proc = subprocess.Popen(
        [
            str(master),
            "--host",
            "127.0.0.1",
            "--port",
            str(port),
            "run-job",
            str(job_path),
            "--resultsDirectory",
            str(results),
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
        text=True,
    )
    time.sleep(0.5)
    worker_procs = [
        subprocess.Popen(
            [
                str(worker),
                "--masterServerHost",
                "127.0.0.1",
                "--masterServerPort",
                str(port),
                "--mockRenderMs",
                "40",
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
            text=True,
        )
        for _ in range(workers)
    ]
    try:
        master_out, master_err = master_proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        master_proc.kill()
        pytest.fail(f"{sanitize}-sanitized master timed out")
    worker_reports = []
    for proc in worker_procs:
        try:
            _, worker_err = proc.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            _, worker_err = proc.communicate()
        worker_reports.append((proc.returncode, worker_err))

    assert master_proc.returncode == 0, (
        f"{sanitize}-sanitized master rc={master_proc.returncode}\n"
        f"stderr tail:\n{master_err[-4000:]}"
    )
    assert "SUMMARY:" not in master_err, master_err[-4000:]
    for rc, err in worker_reports:
        assert rc != 66 and "SUMMARY:" not in err, err[-4000:]
        # Not just "the binaries started": each instrumented worker must
        # have completed the 3-step handshake, received the job broadcast,
        # and run the frame exchange through to the trace hand-off — the
        # protocol paths are exactly where the hand-threaded daemons race.
        assert "Job started." in err, (
            f"{sanitize}-sanitized worker never completed the handshake/"
            f"job-start exchange:\n{err[-4000:]}"
        )
        assert "Job finished; sending trace." in err, (
            f"{sanitize}-sanitized worker never reached the job-finished "
            f"exchange:\n{err[-4000:]}"
        )
    rendered = sorted((tmp_path / "frames").glob("rendered-*.png"))
    assert len(rendered) == frames
