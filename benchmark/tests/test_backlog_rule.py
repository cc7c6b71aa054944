"""What a run needs of its job, as data and as a rule (pure Python, no
process started): each backlog configuration's smallest backlog holds the
rate it states, a seed's first frame lies inside the stated span, and the
exit of a child means what `drivers/backlog.py::child_exit` says it means.
"""

import json
from pathlib import Path

import pytest

from benchmark.drivers import backlog
from benchmark.lib import manifest

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = manifest.load_benchmark(ROOT)
# configuration: (smallest backlog, the least rate it has to hold, the ledger's rates of its cells, PR 43)
BACKLOG_CONFIGS = {
    "04vs-14400f-1w": (13681, 290.0, [34.681]),
    "04vs-14400f-4w": (13681, 290.0, [130.17]),
    "03ph2mesh-480f-1w": (178, 3.5, [2.0733, 2.0653]),
    "03ph2scan-480f-1w": (178, 3.5, [1.5885]),
    "03ph2assets-480f-1w": (178, 3.5, [1.9527]),
}
SEEDS = [0, 1, 11, 4400000101, 2**31 - 1, 2**31, 2**31 + 12345, *range(5000, 5400)]


def config_of(name: str) -> dict:
    (entry,) = [c for c in BENCHMARK["configs"] if c["name"] == name]
    return json.loads((ROOT / entry["file"]).read_text())


def test_these_are_the_configurations_the_backlog_driver_runs():
    driven = set()
    for workload in BENCHMARK["workloads"]:
        cell = manifest.load_cell(workload["name"], ROOT)
        if cell.traffic["driver"] == "backlog":
            driven.add(cell.config_name)
    assert driven == set(BACKLOG_CONFIGS)


@pytest.mark.parametrize("name", BACKLOG_CONFIGS)
def test_the_smallest_backlog_holds_the_rate_the_configuration_states(name):
    smallest, at_least, ledger_rates = BACKLOG_CONFIGS[name]
    config = config_of(name)
    start, stated = config["frame_range_from"], config["holds_frames_per_s"]
    assert backlog.smallest_backlog(config) == smallest == config["frames"] - (start["first"] + start["span"] - 1) + 1
    seconds = stated["lull_s"] + BENCHMARK["run_seconds"] + stated["margin_s"]
    held = (smallest - stated["warmup_frames"]) / seconds
    assert backlog.held_rate(config, BENCHMARK["run_seconds"]) == pytest.approx(held)
    assert stated["value"] == pytest.approx(held, rel=0.002)  # the stated number is the formula's, rounded
    assert held >= at_least
    assert all(held >= 1.7 * rate for rate in ledger_rates)
    # the margins are the driver's: its lull is a second at the most
    assert stated["lull_s"] == 1.0 and 0.0 < stated["margin_s"] <= 1.0
    assert "smallest backlog - warmup_frames" in stated["why"]


@pytest.mark.parametrize("name", BACKLOG_CONFIGS)
def test_a_seeds_first_frame_lies_inside_the_span(name, tmp_path):
    (workload,) = [w for w in BENCHMARK["workloads"] if w["config"] == name][:1]
    cell = manifest.load_cell(workload["name"], ROOT)
    start = cell.config["frame_range_from"]
    firsts = set()
    for seed in SEEDS:
        _, first, last = backlog.render_job_file(cell, seed, tmp_path / "job.toml")
        assert start["first"] <= first < start["first"] + start["span"] and last == cell.config["frames"]
        assert last - first + 1 >= backlog.smallest_backlog(cell.config)
        firsts.add(first)
    assert len(firsts) > min(start["span"], len(SEEDS)) // 2  # the seeds spread over the span
    if start["span"] <= 16:
        assert firsts == set(range(start["first"], start["first"] + start["span"]))  # the smallest backlog is drawn


def test_a_configuration_that_states_nothing_holds_nothing():
    assert backlog.held_rate({"frames": 600, "frame_range_from": {"first": 1, "span": 300}}, 45) is None


@pytest.mark.parametrize("phase,code,job_done,verdict", [
    ("window", 0, True, "job_ended"),    # the master, or a worker, 0 inside the window: the backlog ran out
    (backlog.TAIL, 0, True, None),       # the master 0 in the traced tail, and a worker 0 with it: the job is done
    (backlog.TAIL, 0, False, "fault"),   # a worker that leaves in the tail with frames of its job not on disk
    ("set-up", 0, True, "job_ended"), ("warm-up", 0, True, "job_ended"),  # any exit before the window fails
    ("set-up", 0, False, "fault"), ("warm-up", 0, False, "fault"), ("window", 0, False, "fault"),
    ("set-up", 1, False, "fault"), ("warm-up", -9, False, "fault"), ("window", 1, True, "fault"),
    (backlog.TAIL, 1, True, "fault"), (backlog.TAIL, -15, True, "fault"), (backlog.TAIL, 2, False, "fault"),
])
def test_what_the_exit_of_a_child_means(phase, code, job_done, verdict):
    assert backlog.child_exit(phase, code, job_done) == verdict


def test_a_job_that_ends_inside_the_window_says_so():
    cell = manifest.load_cell("04vs-4w-batch", ROOT)
    window_start = 1000.0
    # 7,201 frames: 150 before the window, the rest at 170.8 frames/s
    seen = {f"rendered-{n:06d}.jpg": (window_start - 1.0 + n / 150.0, 90_000) for n in range(150)}
    seen.update({f"rendered-{150 + n:06d}.jpg": (window_start + (n + 1) / 170.8, 90_000) for n in range(7051)})
    message = backlog.job_ended_message(cell, 7201, seen, window_start, 45.0)
    assert "backlog of 7201 frames" in message and "41.3 s into the window of 45 s" in message
    assert "at 170.8 frames/s" in message and "frame_range_from of 04vs-14400f-4w holds to 291 frames/s" in message
    assert "\n" not in message
    before = backlog.job_ended_message(cell, 12, {"rendered-000001.jpg": (990.0, 1)}, None, 45.0)
    assert "backlog of 12 frames ended before the window began" in before and "holds to 291" in before
