"""The cell `svc2fam-4w-closed12` as data: it finds its files, its eight
metrics find their readers, a program without the pool's series gives them
nothing to read, and a whole run of it walks through on the CPU.

The rehearsal starts `master serve` and FOUR workers as real processes and
serves both families at 64x64 through the Pallas interpreter, about three
minutes on eight cores; it has a time limit of its own. Untraced, as
`test_service_cell.py`'s.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
CELL = "svc2fam-4w-closed12"
REHEARSAL_SECONDS = 1200
NEW_METRICS = {
    "pool_slot_empty_share", "pool_worker_frames_spread", "pool_job_workers_mean",
    "pool_announce_all_ready_ms_mean", "pool_jobs_per_min", "pool_master_cpu_share",
    "pool_units_rendered_twice", "pool_prepare_s_total",
}


def test_the_cell_and_its_metrics_find_their_files():
    assert manifest.validate(ROOT) == []
    listing = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert listing.returncode == 0 and listing.stderr == "" and CELL in listing.stdout
    benchmark = manifest.load_benchmark(ROOT)
    assert len(benchmark["workloads"]) == 8 and sum(w["chips"] == 4 for w in benchmark["workloads"]) == 2
    cell = manifest.load_cell(CELL, ROOT)
    assert cell.chips == 4 and cell.config["workers"] == 4
    assert cell.traffic["driver"] == "service_pool" and cell.traffic["jobs_in_hand"] == 12
    assert cell.traffic["warmup_jobs"] == 20 and cell.traffic["warmup_every_worker"] is True
    wanted = {m["name"] for m in benchmark["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    names = {metric["name"] for metric in cell.per_layer}
    assert names == wanted and NEW_METRICS < names
    assert all(m["workloads"] == [CELL] for m in benchmark["per_layer"] if m["name"] in NEW_METRICS)
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}
    # the one-worker configuration on four workers: the same families, mix, sizes and check
    single = manifest.load_cell("svc2fam-1w-closed3", ROOT).config
    for key in ("families", "sequence", "job_name_format", "output_directory_format", "trace_slice_s"):
        assert cell.config[key] == single[key], key
    assert cell.config["check"]["frames_per_family"] == single["check"]["frames_per_family"] == 1
    assert set(single["guarantees"]) < set(cell.config["guarantees"])
    assert "no_frame_rendered_twice_without_cause" in cell.config["guarantees"]
    entry = next(c for c in benchmark["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == ["workers", "frame_range_from", "samples", "jobs"] == list(cell.config["reduced"])
    assert len({c["source"] for c in benchmark["configs"]}) == len({c["file"] for c in benchmark["configs"]})


def test_the_new_readers_find_nothing_in_a_program_without_the_series():
    """The parent's side of a line: no series, no value, no exception
    (`pool_jobs_per_min` reads a counter the parent has, and not here)."""
    empty = {
        "window_s": 45.0, "workers": 4, "frames_per_s": 60.0, "files": [], "cache_entries_delta": 0,
        "render": {"width": 512, "height": 512, "samples": 7.6, "max_bounces": 4},
        "scrapes": {"master": ([{}], [{}]), "workers": ([{}] * 4, [{}] * 4)}, "trace": None, "jobs": [],
    }
    assert {readers.read_metric(name, empty, ROOT) for name in NEW_METRICS} == {None}


def test_the_new_readers_read_the_series_the_program_feeds():
    key = lambda name, **labels: (name, tuple(sorted(labels.items())))  # noqa: E731
    master_before = {
        key("sched_jobs_finished_total"): 20.0, key("master_process_cpu_seconds_total"): 3.0,
        key("sched_job_worker_units_sum"): 50.0, key("sched_job_worker_units_count"): 20.0,
        key("sched_job_announce_seconds_sum", edge="all_ready"): 1.0,
        key("sched_job_announce_seconds_count", edge="all_ready"): 20.0,
        key("sched_job_announce_seconds_sum", edge="first_ready"): 0.2,
        key("sched_job_announce_seconds_count", edge="first_ready"): 20.0,
        key("sched_units_rendered_twice_total", cause="none"): 0.0,
    }
    master_after = {
        key("sched_jobs_finished_total"): 125.0, key("master_process_cpu_seconds_total"): 12.0,
        key("sched_job_worker_units_sum"): 407.0, key("sched_job_worker_units_count"): 125.0,
        key("sched_job_announce_seconds_sum", edge="all_ready"): 3.1,
        key("sched_job_announce_seconds_count", edge="all_ready"): 125.0,
        key("sched_job_announce_seconds_sum", edge="first_ready"): 0.9,
        key("sched_job_announce_seconds_count", edge="first_ready"): 125.0,
        key("sched_units_rendered_twice_total", cause="none"): 0.0,
        key("sched_units_rendered_twice_total", cause="preemption"): 2.0,
    }
    frames = (700.0, 650.0, 720.0, 730.0)
    workers_before = [{key("worker_frames_rendered_total"): 100.0, key("worker_loop_seconds_total", state="no_work"): 1.0}] * 4
    workers_after = [
        {
            key("worker_frames_rendered_total"): 100.0 + rendered,
            key("worker_loop_seconds_total", state="no_work"): 1.0 + 0.9 * (index + 1),
            key("worker_loop_seconds_total", state="report"): 5.0,
        } for index, rendered in enumerate(frames)
    ]
    run = {
        "window_s": 45.0, "workers": 4,
        "scrapes": {"master": ([master_before], [master_after]), "workers": (workers_before, workers_after)},
        "pool": {"prepare_built_s": [25.0, 26.0, 27.0, 28.0]},
    }
    values = {name: readers.read_metric(name, run, ROOT) for name in NEW_METRICS}
    assert values == {
        "pool_slot_empty_share": 100.0 * (0.9 + 1.8 + 2.7 + 3.6) / 180.0,
        "pool_worker_frames_spread": 100.0 * 80.0 / 700.0,
        "pool_job_workers_mean": 357.0 / 105.0,
        "pool_announce_all_ready_ms_mean": 1000.0 * (3.1 - 1.0) / 105.0,
        "pool_jobs_per_min": 140.0,
        "pool_master_cpu_share": 20.0,
        "pool_units_rendered_twice": 2.0,
        "pool_prepare_s_total": 4 * 25.0 + 6.0,
    }


def test_prepare_seconds_count_the_preparations_that_built_something(tmp_path):
    from benchmark.drivers import service_pool

    span = lambda seconds, **args: {"ph": "X", "name": "job_prepare", "cat": "worker.prepare", "dur": seconds * 1e6, "args": args}  # noqa: E731
    timeline = tmp_path / "worker-0_trace-events.json"
    timeline.write_text(json.dumps({"traceEvents": [
        span(36.0, family="03_physics-2-scan", resident=False), span(30.0, family="03_physics-2-scan", resident=True),
        span(12.5, family="04_very-simple", resident=False), span(0.0001, family="04_very-simple", resident=True),
        {"ph": "X", "name": "render", "cat": "worker", "dur": 29000.0, "args": {"frame": 1, "job": "a"}},
    ]}))
    assert service_pool._prepare_built_seconds(timeline) == 48.5
    timeline.write_text(json.dumps({"traceEvents": [{"ph": "X", "name": "job_prepare", "dur": 5e6, "args": {"family": "x"}}]}))
    assert service_pool._prepare_built_seconds(timeline) is None  # a program whose spans do not say


def test_every_worker_gets_a_unit_of_its_own_to_check_over_as_few_frame_numbers_as_cover_the_pool():
    from benchmark.drivers.service_pool import _a_unit_of_each_worker

    # a family that wrapped: frame 304 lies in two jobs, on two workers
    rendered = {
        "w0": [("scan-1", 300), ("scan-1", 304)], "w1": [("scan-9", 304), ("scan-9", 305)],
        "w2": [("scan-1", 301), ("scan-2", 310)], "w3": [("scan-2", 310), ("scan-2", 311)], "w4": [("scan-3", 400)],
    }
    on_disk = {unit: None for units in rendered.values() for unit in units} - {("scan-3", 400): None}.keys()
    chosen = _a_unit_of_each_worker(rendered, on_disk, 304)
    assert chosen == {"w0": ("scan-1", 304), "w1": ("scan-9", 304), "w2": ("scan-2", 310), "w3": ("scan-2", 310)}
    # nobody rendered the family's own frame: the numbers most workers share, then the lowest
    assert _a_unit_of_each_worker(rendered, on_disk, 999) == {
        "w0": ("scan-1", 304), "w1": ("scan-9", 304), "w2": ("scan-2", 310), "w3": ("scan-2", 310),
    }
    assert _a_unit_of_each_worker({}, on_disk, 304) == {}


def test_the_join_is_asked_over_one_connection_however_long_the_workers_take():
    """A control plane that comes up late and counts four workers only at its
    fortieth answer: one connection accepted, not forty (each closed one would
    hold a port of the range the workers' telemetry ports were drawn from)."""
    import socket
    import threading

    from benchmark.drivers import service_pool
    from benchmark.lib import launch

    port, accepted, asked = launch.free_port(), [], []

    def serve():
        time.sleep(0.6)  # refused until then: the master is not listening yet
        with socket.socket() as server:
            server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            server.bind(("127.0.0.1", port))
            server.listen(8)
            connection, _ = server.accept()
            accepted.append(connection)
            with connection, connection.makefile("rb") as reader:
                while reader.readline():
                    asked.append(1)
                    workers = 4 if len(asked) >= 40 else len(asked) % 4
                    connection.sendall(json.dumps({"ok": True, "sched": {"rebalance": {"workers": workers}}}).encode() + b"\n")

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    service_pool._until_joined(launch.Processes(), port, 4)
    thread.join(5.0)
    assert len(accepted) == 1 and len(asked) == 40


def test_a_worker_that_does_not_answer_a_warm_up_scrape_is_not_warm_yet():
    from benchmark.drivers import service_pool
    from benchmark.lib import launch

    assert service_pool._pool_is_warm([launch.free_port()], []) is False  # nobody listens there


def test_a_whole_run_of_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "4300000111",
         "--seconds", "20", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    window = next(line for line in lines if line["stage"] == "window")
    assert all(family["files"] > 0 for family in window["families"].values())
    service = next(line for line in lines if line["stage"] == "service")
    assert service["problems"] == 0 and service["must"] > 400
    pool = next(line for line in lines if line["stage"] == "pool")["workers"]
    assert len(pool) == 4 and all(min(worker["by_family"].values()) > 0 for worker in pool)
    twice = next(line for line in lines if line["stage"] == "rendered_twice")
    assert twice["unexplained"] == []
    checked = next(line for line in lines if line["stage"] == "check")
    for family in window["families"]:  # one frame a family of EVERY worker, each by a share or as the family's frame
        by_worker = checked[family]["by_worker"]
        assert len(by_worker) == 4 and all(one.get("agreement", 1.0) >= 0.97 for one in by_worker.values())
