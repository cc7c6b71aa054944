#!/usr/bin/env python
"""Reference-scale demonstration: 14400 frames x 40 workers (C++ daemons).

The reference's primary measured workload is the 04_very-simple
14400-frame job at cluster sizes up to 40/80 workers on SLURM
(reference: blender-projects/04_very-simple/04_very-simple_measuring_14400f-40w_dynamic.toml,
scripts/arnes/queue-batch_04vs_14400f-40w_dynamic.sh — 160 min budget).
This script runs the SAME workload shape — 14400 frames, 40 worker
processes, dynamic and tpu-batch strategies — through the native C++
master + 40 C++ mock workers on localhost, then validates the trace with
the reference analysis loader and records a compact summary.

The mock render time (default 25 ms) stands in for Blender so the run
stresses what this demo is about: master control-plane throughput at
reference scale (~1600 frame-RPCs/s cluster-wide), O(frames) state
handling, and tail behavior — not raytracing speed (the benchmark,
benchmark/run.py, covers that).

The 14400-frame raw trace (~10 MB JSON) is deliberately written to a
scratch directory and NOT committed; what lands in results/ is
SUMMARY.json plus the (small) processed-results file. Reproduce with:
    python scripts/run-scale-demo.py --out results/cluster-runs/scale-14400f-40w
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

FRAMES = 14400
WORKERS = 40  # default; --workers overrides (the reference measured up to 80)
# 100 ms mock frames: long enough that the per-frame master round-trip
# (all 81 processes share one host here, unlike the reference's SLURM
# nodes) amortizes and utilization reflects the scheduler, not localhost
# contention; still ~40 s per strategy run.
MOCK_MS = 100

DYNAMIC = """strategy_type = "dynamic"
target_queue_size = 4
min_queue_size_to_steal = 2
min_seconds_before_resteal_to_elsewhere = 40
min_seconds_before_resteal_to_original_worker = 80"""

TPU_BATCH = """strategy_type = "tpu-batch"
target_queue_size = 4
min_queue_size_to_steal = 2
min_seconds_before_resteal_to_elsewhere = 1
min_seconds_before_resteal_to_original_worker = 2"""

# Reference sequential-baseline semantics: 1 worker, eager-naive-coarse
# with a deep queue (reference BASELINE.md "Strategies measured": tqs=100
# for 1w; speedup = mean 1w time / mean parallel time,
# reference analysis/speedup.py:35-40).
BASELINE_1W = """strategy_type = "eager-naive-coarse"
target_queue_size = 100"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_job(directory: Path, strategy_lines: str, frames_dir: Path) -> Path:
    job_path = directory / "job.toml"
    job_path.write_text(
        f'''
job_name = "04_very-simple_scale"
job_description = "reference-scale 14400f-40w demonstration (mock render)"
project_file_path = "%BASE%/project.blend"
render_script_path = "%BASE%/script.py"
frame_range_from = 1
frame_range_to = {FRAMES}
wait_for_number_of_workers = {WORKERS}
output_directory_path = "{frames_dir}"
output_file_name_format = "rendered-#####"
output_file_format = "PNG"

[frame_distribution_strategy]
{strategy_lines}
'''
    )
    return job_path


def run_one(strategy_name: str, strategy_lines: str, scratch: Path,
            kill: int = 0, kill_after: float = 3.0) -> dict:
    from tpu_render_cluster.native import build_master_daemon, build_worker_daemon

    master = build_master_daemon()
    worker = build_worker_daemon()
    assert master is not None and worker is not None, "native build failed"

    run_dir = scratch / strategy_name
    frames_dir = run_dir / "frames"
    results_dir = run_dir / "results"
    run_dir.mkdir(parents=True)
    port = free_port()
    job_path = write_job(run_dir, strategy_lines, frames_dir)

    master_args = [
        str(master), "--host", "127.0.0.1", "--port", str(port),
        "run-job", str(job_path), "--resultsDirectory", str(results_dir),
    ]
    if kill:
        # Chaos runs need prompt failure detection: evict after 5 s of
        # heartbeat silence instead of the 120 s default.
        master_args += ["--evictAfterSeconds", "5"]
    master_proc = subprocess.Popen(
        master_args, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    worker_procs: list[subprocess.Popen] = []
    try:
        time.sleep(1.0)  # accept-loop lead time at 40-connection scale
        worker_procs = [
            subprocess.Popen(
                [str(worker), "--masterServerHost", "127.0.0.1",
                 "--masterServerPort", str(port),
                 "--mockRenderMs", str(MOCK_MS)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )
            for _ in range(WORKERS)
        ]
        t0 = time.perf_counter()
        if kill:
            # Kill only once the job is actually rendering: a victim that
            # dies BEFORE registering would hold the barrier at
            # wait_for_number_of_workers forever and the run would
            # demonstrate nothing about eviction.
            deadline = time.perf_counter() + 60
            while (
                not any(frames_dir.glob("rendered-*"))
                and time.perf_counter() < deadline
            ):
                time.sleep(0.1)
            time.sleep(kill_after)
            for victim in worker_procs[:kill]:
                victim.kill()
        # Ceiling scales with the configured workload ON THE SURVIVORS:
        # --workers 1 at 100 ms frames legitimately needs
        # FRAMES * MOCK_MS seconds.
        ideal_s = FRAMES * MOCK_MS / 1000.0 / max(1, WORKERS - kill)
        rc = master_proc.wait(timeout=120 + 3 * ideal_s)
        wall = time.perf_counter() - t0
        for proc in worker_procs:
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
        assert rc == 0, f"master exited rc={rc}"
    finally:
        # A timeout/assert above must not leak 41 daemons.
        if master_proc.poll() is None:
            master_proc.kill()
        for proc in worker_procs:
            if proc.poll() is None:
                proc.kill()

    rendered = len(list(frames_dir.glob("rendered-*")))
    assert rendered == FRAMES, f"expected {FRAMES} outputs, found {rendered}"

    raw_trace = next(results_dir.glob("*_raw-trace.json"))

    if kill:
        # Evicted workers contribute no trace, so the (reference-mirrored)
        # strict worker-count validation rightly rejects chaos traces;
        # account from the raw JSON instead. The completion proof is the
        # frame count on disk plus per-survivor render totals.
        data = json.loads(raw_trace.read_text())
        duration = (data["master_trace"]["job_finish_time"]
                    - data["master_trace"]["job_start_time"])
        survivors = data["worker_traces"]
        rendered_by_survivors = sum(
            len(w["frame_render_traces"]) for w in survivors.values()
        )
        util = {"n/a": "evicted workers void the utilization contract"}
        tail = {
            "survivors": len(survivors),
            "frames_rendered_by_survivors": rendered_by_survivors,
        }
    else:
        # Our analysis pipeline.
        from tpu_render_cluster.analysis.models import JobTrace
        from tpu_render_cluster.analysis.metrics import (
            tail_delay_stats,
            utilization_stats,
        )

        trace = JobTrace.load_from_trace_file(raw_trace)
        duration = trace.job_finished_at - trace.job_started_at
        # Stats dicts are keyed by (cluster_size, strategy) tuples;
        # stringify for JSON.
        util = {
            f"{k[0]}w_{k[1]}": v
            for k, v in utilization_stats([trace]).items()
        }
        tail = {
            f"{k[0]}w_{k[1]}": v
            for k, v in tail_delay_stats([trace]).items()
        }

    # Acceptance: the REFERENCE's loader parses the same file (its
    # validation includes the worker-count invariant, reference
    # analysis/core/models.py:278-282). Only applicable to strategy tags
    # the reference's enum knows — `tpu-batch` is this repo's addition, so
    # its traces are validated by our loader alone.
    reference_loader = "n/a (novel strategy tag)"
    if kill:
        reference_loader = "n/a (evicted workers void the count invariant)"
    elif strategy_name in ("naive-fine", "eager-naive-coarse", "dynamic"):
        sys.path.insert(0, "/root/reference/analysis")
        try:
            from core.models import JobTrace as RefJobTrace  # type: ignore

            ref_trace = RefJobTrace.load_from_trace_file(raw_trace)
            assert len(ref_trace.worker_traces) == WORKERS
            reference_loader = True
        finally:
            sys.path.pop(0)
            for name in [
                n for n in sys.modules
                if n == "core" or n.startswith("core.")
            ]:
                del sys.modules[name]

    summary = {
        "strategy": strategy_name,
        "workers_killed": kill,
        "frames": FRAMES,
        "workers": WORKERS,
        "mock_render_ms": MOCK_MS,
        "job_duration_s": round(duration, 3),
        "master_frame_throughput_fps": round(FRAMES / duration, 1),
        "wall_clock_s": round(wall, 3),
        "utilization": util,
        "tail_delay": tail,
        "reference_loader_ok": reference_loader,
    }
    # Keep the small processed-results file for the record.
    processed = list(results_dir.glob("*_processed-results.json"))
    summary["processed_results_file"] = processed[0].name if processed else None
    summary["_raw_trace_scratch"] = str(raw_trace)
    return summary


def main() -> int:
    global WORKERS, MOCK_MS, FRAMES
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    parser.add_argument(
        "--frames", type=int, default=FRAMES,
        help="frame count (default: the reference's 14400; smaller values "
        "are for smoke-testing the harness itself)",
    )
    parser.add_argument(
        "--workers", type=int, default=WORKERS,
        help="cluster size (reference sizes: 1,5,10,20,40,80)",
    )
    parser.add_argument(
        "--mockRenderMs", dest="mock_ms", type=int, default=MOCK_MS,
    )
    parser.add_argument(
        "--kill", type=int, default=0,
        help="chaos: SIGKILL this many workers a few seconds into each "
        "run; the master must evict them, requeue their frames, and "
        "still finish all 14400 (beyond-reference failure recovery, "
        "SURVEY 5.3).",
    )
    parser.add_argument(
        "--killAfter", dest="kill_after", type=float, default=3.0,
    )
    parser.add_argument(
        "--with-baseline", action="store_true",
        help="also run the 1-worker eager-naive-coarse sequential baseline "
        "(same frames x mock_ms workload) and write the full analysis "
        "statistics — incl. speedup/efficiency — for this population "
        "under results/analysis/scale-14400f-<W>w/. The baseline leg "
        "takes 14400 * mockRenderMs of real time.",
    )
    args = parser.parse_args()
    WORKERS = args.workers
    MOCK_MS = args.mock_ms
    FRAMES = args.frames
    if args.kill and not 0 < args.kill < WORKERS:
        parser.error(
            f"--kill must leave at least one survivor (0 < kill < {WORKERS})"
        )
    if args.out is None:
        # The frame count is part of the population name so a smoke run
        # (--frames 120) can never overwrite the recorded 14400-frame
        # populations or their analysis directories.
        args.out = f"results/cluster-runs/scale-{FRAMES}f-{WORKERS}w"
    out_dir = REPO_ROOT / args.out
    out_dir.mkdir(parents=True, exist_ok=True)

    scratch = Path(tempfile.mkdtemp(prefix="trc-scale-"))
    summaries = []
    try:
        runs = [("dynamic", DYNAMIC), ("tpu-batch", TPU_BATCH)]
        if args.with_baseline:
            # The sequential baseline that makes speedup/efficiency
            # computable for this population (same frames x mock_ms
            # workload on ONE worker — 14400 * mock_ms seconds of real
            # time, so this is the long leg of the run).
            runs.append(("eager-naive-coarse-1w-baseline", BASELINE_1W))
        for name, lines in runs:
            baseline_run = name.endswith("1w-baseline")
            cluster = 1 if baseline_run else args.workers
            WORKERS = cluster  # run_one/write_job read the global
            print(f"=== {name}: {FRAMES}f x {cluster}w ===", flush=True)
            summary = run_one(
                name, lines, scratch,
                kill=0 if baseline_run else args.kill,
                kill_after=args.kill_after,
            )
            print(json.dumps(
                {k: v for k, v in summary.items() if not k.startswith("_")
                 and k not in ("utilization", "tail_delay")},
            ), flush=True)
            # Preserve the small processed-results next to the summary.
            raw_trace = Path(summary.pop("_raw_trace_scratch"))
            processed = list(raw_trace.parent.glob("*_processed-results.json"))
            if processed:
                shutil.copy(
                    processed[0], out_dir / f"{name}_{processed[0].name}"
                )
            summaries.append(summary)

        if args.with_baseline and not args.kill:
            # With the 1w baseline in the same trace population, the full
            # analysis pipeline produces non-empty speedup/efficiency for
            # this cluster size (reference analysis/speedup.py:35-40
            # semantics). Raw 14400-frame traces stay in scratch; only the
            # computed statistics/plots are committed.
            from tpu_render_cluster.analysis import run_all as analysis

            canonical = REPO_ROOT / "results" / "cluster-runs"
            if out_dir.parent == canonical:
                analysis_out = REPO_ROOT / "results" / "analysis" / out_dir.name
            else:  # smoke-test runs keep their analysis next to their out
                analysis_out = out_dir / "analysis"
            rc = analysis.main(
                ["--results", str(scratch), "--out", str(analysis_out)]
            )
            assert rc == 0, "analysis pipeline failed on the scale traces"
            stats = json.loads((analysis_out / "statistics.json").read_text())
            assert stats["speedup"], (
                "speedup must populate once the 1w baseline is present"
            )
            print(
                f"analysis -> {analysis_out} "
                f"(speedup keys: {list(stats['speedup'])})",
                flush=True,
            )
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    (out_dir / "SUMMARY.json").write_text(json.dumps(summaries, indent=2) + "\n")
    print(f"summary -> {out_dir / 'SUMMARY.json'}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
