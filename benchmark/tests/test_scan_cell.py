"""The cell `03ph2scan-1w-queued` as data: it finds its files, its metrics
find their readers, and a whole run of it walks through on the CPU.

The rehearsal starts a master and a worker as real processes and renders
64x64 frames of the real 871,200-triangle scene through the Pallas
interpreter, about two minutes; it has a time limit of its own. Untraced:
a profile of interpreted kernels is millions of host events and is not
written inside the harness's limit, which says nothing of the chip.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.lib import manifest, readers

ROOT = Path(__file__).resolve().parents[2]
CELL = "03ph2scan-1w-queued"
REHEARSAL_SECONDS = 600
# first frame 296: the checked frames are 304 and 308, twelve and sixteen frames in, so a slow
# host still has them on disk when the window ends
SEED = "3200000222"
NEW_METRICS = {"treelet_fetch_MB_per_frame", "walk_node_visits_per_ray", "geometry_hbm_MB", "walk_hbm_roofline_share"}


def test_the_cell_and_its_metrics_find_their_files():
    assert manifest.validate(ROOT) == []
    cell = manifest.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.config["deployment"]["triangles_per_body"] == 871_200
    assert cell.traffic["strategy"]["strategy_type"] == "tpu-batch"
    names = {metric["name"] for metric in cell.per_layer}
    # every accepted metric whose layer the cell runs reads here under its accepted name; the count
    # is the manifest's own: every metric without a list, and those that list the cell
    benchmark = manifest.load_benchmark(ROOT)
    wanted = {m["name"] for m in benchmark["per_layer"] if "workloads" not in m or CELL in m["workloads"]}
    assert NEW_METRICS < names and names == wanted
    assert {"kernel_ms_per_frame", "device_wait_ms_per_frame", "host_syncs_per_frame",
            "masked_tier_frame_share", "pool_live_lane_share", "compiles_in_window"} < names
    assert "wavefront_launch_occupancy" not in names  # the naive-fine cell's name for the same counts
    assert {metric["name"] for metric in cell.end_to_end} == {"frames_per_s", "setup_s"}
    assert cell.config["check"]["independent"]["reference"] == "plain_tracer_accel"


def test_the_new_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's side of a traced run: no series, no trace, no value,
    and no exception."""
    empty = {
        "window_s": 45.0, "workers": 1, "frames_per_s": 1.0, "files": [], "cache_entries_delta": 0,
        "render": {"width": 512, "height": 512, "samples": 1, "max_bounces": 4},
        "scrapes": {"master": ([{}], [{}]), "workers": ([{}], [{}])}, "trace": None,
    }
    values = {name: readers.read_metric(name, empty, ROOT) for name in NEW_METRICS}
    assert set(values.values()) == {None}


def test_the_roofline_share_is_bytes_over_what_hbm_moves_in_the_kernels_time():
    from benchmark.lib.peaks import chip_peaks
    from benchmark.lib.walk_bytes import LANE_BYTES_READ, LANE_BYTES_WRITTEN, frame_walk_bytes

    assert (LANE_BYTES_READ, LANE_BYTES_WRITTEN) == (44, 60)
    assert frame_walk_bytes(1e9, 1e6) == 1e9 + 104e6
    frames, lanes, fetched = 10.0, 573_440.0, 15e9
    key = lambda name, **labels: (name, tuple(sorted(labels.items())))  # noqa: E731
    before = {key("worker_frame_phase_seconds_count", phase="render"): 0.0}
    after = {
        key("worker_frame_phase_seconds_count", phase="render"): frames,
        key("render_treelet_fetch_bytes_total"): fetched * frames,
        key("render_pool_launched_lanes_total"): lanes * frames,
        key("render_device_units", kind="TPU v5 lite", platform="tpu"): 1.0,
    }
    run = {
        "frames_per_s": 1.0, "scrapes": {"workers": ([before], [after])},
        "trace": {"devices": [{"slice_s": 10.0, "busy_s": 9.9, "kernel_s": 9.0}]},
    }
    share = readers.read_metric("walk_hbm_roofline_share", run, ROOT)
    expected = 100.0 * (fetched + lanes * 104) / (0.9 * chip_peaks("TPU v5 lite")["hbm_bytes_per_s"])
    assert abs(share - expected) < 1e-9 and 0 < share < 100


def test_a_whole_run_of_the_cell_rehearses_on_the_cpu():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", SEED,
         "--seconds", "45", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=REHEARSAL_SECONDS,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 20
    assert result["device"]["platform"] == "cpu"  # a rehearsal never passes for a chip run
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
