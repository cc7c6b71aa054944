"""The eight per-layer metrics of PR 54 (the host's CPU), counted in tier-1.

Each reads a `/metrics` delta of a series this PR's program feeds: the
worker's process CPU by mode, a step's own thread's CPU, `file_write` by
operation, and the late loop's seconds by cause. Held here: the eight
entries are the LAST eight of `per_layer`, in the issue's order, with
nothing before them come or gone; each finds its file (five are data for
the accepted `delta_ratio` reader, three have a module in
`pool_master_cpu_share.py`'s form) in every cell (ten at PR 54); each
gives, on two scrapes written out by hand, the number worked out by hand,
and `None` (no exception) on a scrape without its series, which is the
parent's side of this PR's pairs; and the program feeds every series the
readers name. In `tests/` because the driver's tier-1 command collects
`tests/` alone (`tests/test_benchmark_save_beside_save_metric.py` has the
same reason).
"""

from pathlib import Path

import pytest

from benchmark.lib import manifest, readers, scrape
from tpu_render_cluster.obs import CPU_TIMED_STEPS, FILE_WRITE_OPS, LoopLagMonitor, MetricsRegistry
from tpu_render_cluster.obs.loopmon import BLOCKED_CAUSES
from tpu_render_cluster.obs.prometheus import render_prometheus
from tpu_render_cluster.traces.worker_trace import WorkerTraceBuilder
from tpu_render_cluster.utils.cancellation import CancellationToken
from tpu_render_cluster.worker.queue import PROCESS_CPU_MODES, WorkerAutomaticQueue

ROOT = Path(__file__).resolve().parents[1]
# what `per_layer` held before PR 54, in its order: 71 entries, the last PR 53's
BEFORE = 71
# (name, unit, layer, whether its reader is a module), in the order the issue's table has them
METRICS = [
    ("host_cpu_ms_per_frame", "ms", "worker runtime", False),
    ("host_cpu_system_share", "%", "worker runtime", False),
    ("worker_cores_busy", "cores", "worker runtime", True),
    ("encode_cpu_ms_per_frame", "ms", "result plane", False),
    ("file_write_cpu_ms_per_frame", "ms", "result plane", False),
    ("device_wait_cpu_ms_per_frame", "ms", "render backend", False),
    ("file_dir_ops_ms_per_frame", "ms", "result plane", True),
    ("process_stopped_s", "s", "worker runtime", True),
]
NAMES = [name for name, _, _, _ in METRICS]
CELLS_AT_PR_54 = [
    "04vs-1w-coarse", "04vs-4w-batch", "03ph2mesh-1w-queued", "03ph2mesh-1w-fine", "03ph2scan-1w-queued",
    "03ph2assets-1w-queued", "svc2fam-1w-closed3", "svc2fam-4w-closed12", "svc2fam-4w-kill1", "04vs-1w-png",
]

# Two workers at the two edges of a window of 20 s, as their `/metrics` say it. Worker 0 rendered
# 1000 frames (100 -> 1100), worker 1 500 (0 -> 500, its series new since the first edge).
EDGE_ONE = ['''
worker_frame_phase_seconds_count{phase="render"} 100
worker_frame_phase_seconds_count{phase="write"} 100
worker_process_cpu_seconds_total{mode="user"} 30.0
worker_process_cpu_seconds_total{mode="system"} 10.0
worker_host_cpu_units 13
worker_frame_step_cpu_seconds_total{step="encode"} 1.0
worker_frame_step_cpu_seconds_total{step="file_write"} 0.5
worker_frame_step_cpu_seconds_total{step="device_wait"} 0.25
worker_frame_step_cpu_seconds_total{step="dispatch"} 7.0
worker_file_write_op_seconds_total{op="mkdir"} 0.1
worker_file_write_op_seconds_total{op="create"} 0.2
worker_file_write_op_seconds_total{op="write"} 0.3
worker_file_write_op_seconds_total{op="close"} 0.4
worker_file_write_op_seconds_total{op="rename"} 0.5
obs_loop_blocked_seconds_total{cause="not_scheduled",role="worker"} 0
obs_loop_blocked_seconds_total{cause="process_busy",role="worker"} 1.0
obs_loop_blocked_seconds_total{cause="process_idle",role="worker"} 0
obs_loop_blocked_seconds_total{cause="process_idle",role="master"} 50.0
''', '''
worker_frame_phase_seconds_count{phase="render"} 0
''']
EDGE_TWO = ['''
worker_frame_phase_seconds_count{phase="render"} 1100
worker_frame_phase_seconds_count{phase="write"} 1100
worker_process_cpu_seconds_total{mode="user"} 36.0
worker_process_cpu_seconds_total{mode="system"} 12.0
worker_host_cpu_units 13
worker_frame_step_cpu_seconds_total{step="encode"} 5.5
worker_frame_step_cpu_seconds_total{step="file_write"} 1.25
worker_frame_step_cpu_seconds_total{step="device_wait"} 0.4
worker_frame_step_cpu_seconds_total{step="dispatch"} 9.0
worker_file_write_op_seconds_total{op="mkdir"} 0.4
worker_file_write_op_seconds_total{op="create"} 1.1
worker_file_write_op_seconds_total{op="write"} 2.3
worker_file_write_op_seconds_total{op="close"} 0.9
worker_file_write_op_seconds_total{op="rename"} 0.8
obs_loop_blocked_seconds_total{cause="not_scheduled",role="worker"} 1.5
obs_loop_blocked_seconds_total{cause="process_busy",role="worker"} 1.75
obs_loop_blocked_seconds_total{cause="process_idle",role="worker"} 0
obs_loop_blocked_seconds_total{cause="process_idle",role="master"} 90.0
''', '''
worker_frame_phase_seconds_count{phase="render"} 500
worker_process_cpu_seconds_total{mode="user"} 3.0
worker_process_cpu_seconds_total{mode="system"} 1.0
worker_frame_step_cpu_seconds_total{step="encode"} 1.5
worker_frame_step_cpu_seconds_total{step="file_write"} 0.75
worker_frame_step_cpu_seconds_total{step="device_wait"} 0.05
worker_file_write_op_seconds_total{op="mkdir"} 0.3
worker_file_write_op_seconds_total{op="create"} 0.3
worker_file_write_op_seconds_total{op="write"} 1.0
worker_file_write_op_seconds_total{op="close"} 0.2
worker_file_write_op_seconds_total{op="rename"} 0.3
obs_loop_blocked_seconds_total{cause="not_scheduled",role="worker"} 0
obs_loop_blocked_seconds_total{cause="process_busy",role="worker"} 0
obs_loop_blocked_seconds_total{cause="process_idle",role="worker"} 2.25
''']
# By hand, over 1500 frames and 20 s: the workers' CPU rose by (6 + 2) + (3 + 1) = 12 s, 3 of them system;
# encode's by 4.5 + 1.5, file_write's by 0.75 + 0.75, device_wait's by 0.15 + 0.05; mkdir + create + rename by
# (0.3 + 0.9 + 0.3) + (0.3 + 0.3 + 0.3) = 2.4 s; the workers' loops were late for 1.5 + 0.75 + 2.25 = 4.5 s,
# 0.75 of them crowded by the process's own threads (the master's 40 s are no worker's).
BY_HAND = {
    "host_cpu_ms_per_frame": 12.0 / 1500 * 1000,
    "host_cpu_system_share": 100.0 * 3.0 / 12.0,
    "worker_cores_busy": 12.0 / 20.0,
    "encode_cpu_ms_per_frame": 6.0 / 1500 * 1000,
    "file_write_cpu_ms_per_frame": 1.5 / 1500 * 1000,
    "device_wait_cpu_ms_per_frame": 0.2 / 1500 * 1000,
    "file_dir_ops_ms_per_frame": 2.4 / 1500 * 1000,
    "process_stopped_s": 4.5 - 0.75,
}


def observed(before: list[str], after: list[str], window_s: float = 20.0) -> dict:
    workers = ([scrape.parse(text) for text in before], [scrape.parse(text) for text in after])
    return {"window_s": window_s, "workers": len(before), "scrapes": {"master": ([{}], [{}]), "workers": workers}}


def test_the_eight_entries_are_the_last_of_per_layer_and_nothing_before_them_has_come_or_gone():
    assert manifest.validate(ROOT) == []
    benchmark = manifest.load_benchmark(ROOT)
    names = [m["name"] for m in benchmark["per_layer"]]
    assert len(names) == len(set(names)) and names[BEFORE:BEFORE + 8] == NAMES
    # what was there: the 71st is PR 53's, the three before it PR 52's, and the two PR 50 and PR 51 brought
    assert names[BEFORE - 1] == "save_beside_save_frame_share"
    assert names[BEFORE - 4:BEFORE - 1] == ["encode_MB_per_s", "held_ms_per_frame", "save_bound_share"]
    assert names[65] == "dispatch_ahead_frame_share" and names[66] == "walk_top_tests_per_entry"
    assert names[0] == "assign_ms_mean" and len(names[:BEFORE]) == 71
    # every cell, then and since: the ten of PR 54 by their places, and whatever later PRs appended
    # behind them (PR 56: `04vs-1w-fine`, `02phmesh-1w-queued`) appended to these eight lists too
    every_cell = [w["name"] for w in benchmark["workloads"]]
    assert every_cell[:10] == CELLS_AT_PR_54 and len(every_cell) == len(set(every_cell))
    layers_before = {m["layer"] for m in benchmark["per_layer"][:BEFORE]}
    for entry, (name, unit, layer, _module) in zip(benchmark["per_layer"][BEFORE:BEFORE + 8], METRICS):
        assert entry == {
            "name": name, "unit": unit, "better": "lower", "source": "program_counter",
            "layer": layer, "moves": "frames_per_s", "workloads": every_cell,
        }
        assert layer in layers_before  # a layer the accepted benchmark names, letter for letter


@pytest.mark.parametrize("name,unit,layer,module", METRICS)
def test_each_metric_finds_its_file_in_every_cell(name, unit, layer, module):
    benchmark = manifest.load_benchmark(ROOT)
    for workload in benchmark["workloads"]:
        cell = manifest.load_cell(workload["name"], ROOT)
        assert name in {m["name"] for m in cell.per_layer}
        assert "frames_per_s" in {m["name"] for m in cell.end_to_end}  # the metric it moves is reported there
    spec, directory = manifest.layer_metric_spec(name, ROOT)
    assert (directory / f"{name}.py").exists() == module
    assert "not on the line for a program without the counter" in spec["what"]
    if module:
        assert set(spec) == {"reader", "what"} and spec["reader"] == "module"
    else:
        assert set(spec) == {"reader", "from", "numerator", "denominator", "scale", "what"}
        assert spec["reader"] == "delta_ratio" and spec["from"] == "workers"


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_gives_the_number_worked_out_by_hand(name):
    run = observed(EDGE_ONE, EDGE_TWO)
    assert readers.read_metric(name, run, ROOT) == pytest.approx(BY_HAND[name], rel=1e-12)
    # one worker alone (the one-chip cells), its own numbers
    alone = observed(EDGE_ONE[:1], EDGE_TWO[:1])
    by_hand_alone = {
        "host_cpu_ms_per_frame": 8.0, "host_cpu_system_share": 25.0, "worker_cores_busy": 0.4,
        "encode_cpu_ms_per_frame": 4.5, "file_write_cpu_ms_per_frame": 0.75, "device_wait_cpu_ms_per_frame": 0.15,
        "file_dir_ops_ms_per_frame": 1.5, "process_stopped_s": 1.5,
    }
    assert readers.read_metric(name, alone, ROOT) == pytest.approx(by_hand_alone[name], rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_gives_nothing_and_raises_nothing_on_a_scrape_without_its_series(name):
    """The parent's side of this PR's pairs: the frames are counted, the
    loop monitor's episodes too, and none of the four new families is
    there."""
    parent = '''
worker_frame_phase_seconds_count{phase="render"} %d
worker_frame_step_seconds_sum{step="encode"} %f
obs_loop_blocked_episodes_total{role="worker"} 0
master_process_cpu_seconds_total 3.0
'''
    run = observed([parent % (100, 1.0)] * 2, [parent % (1100, 6.0)] * 2)
    assert readers.read_metric(name, run, ROOT) is None
    assert readers.read_metric(name, observed([""], [""]), ROOT) is None  # a worker that exposes nothing at all


def test_a_run_without_a_stop_reads_zero_and_not_nothing():
    quiet = "\n".join(
        f'obs_loop_blocked_seconds_total{{cause="{cause}",role="worker"}} 0' for cause in BLOCKED_CAUSES
    )
    assert readers.read_metric("process_stopped_s", observed([quiet], [quiet]), ROOT) == 0.0
    # crowded by its own encoders and by nothing else: late, and no stop
    crowded = quiet.replace('cause="process_busy",role="worker"} 0', 'cause="process_busy",role="worker"} 3.5')
    assert readers.read_metric("process_stopped_s", observed([quiet], [crowded]), ROOT) == 0.0
    # a window without frames has no per-frame cost, and one without CPU no share of it
    no_frames = observed(EDGE_ONE[:1], EDGE_ONE[:1])
    for name in NAMES:
        if name.endswith("_per_frame") or name == "host_cpu_system_share":
            assert readers.read_metric(name, no_frames, ROOT) is None, name
    assert readers.read_metric("worker_cores_busy", no_frames, ROOT) == 0.0


def test_the_program_feeds_every_series_the_readers_name():
    """What a worker's `/metrics` says at its start, read by the readers:
    the names and label values in the data files and modules are the
    program's own, letter for letter."""
    registry = MetricsRegistry()
    WorkerAutomaticQueue(None, None, WorkerTraceBuilder(), CancellationToken(), metrics=registry)
    LoopLagMonitor(registry, role="worker")  # as worker/runtime.py makes it
    at_start = scrape.parse(render_prometheus(registry.snapshot()))
    series = {name for name, _ in at_start}
    assert {
        "worker_process_cpu_seconds_total", "worker_frame_step_cpu_seconds_total",
        "worker_file_write_op_seconds_total", "obs_loop_blocked_seconds_total", "worker_host_cpu_units",
    } <= series
    for mode in PROCESS_CPU_MODES:
        assert scrape.total(at_start, "worker_process_cpu_seconds_total", {"mode": mode}) is not None
    for step in CPU_TIMED_STEPS:  # the three steps a reader names, and they alone
        assert scrape.total(at_start, "worker_frame_step_cpu_seconds_total", {"step": step}) == 0.0
    assert scrape.total(at_start, "worker_frame_step_cpu_seconds_total") == 0.0
    for op in FILE_WRITE_OPS:
        assert scrape.total(at_start, "worker_file_write_op_seconds_total", {"op": op}) == 0.0
    assert scrape.total(at_start, "worker_host_cpu_units") >= 1.0
    # every reader but the per-frame ones (no frame yet) finds its series on that scrape alone
    later = dict(at_start)
    later[("worker_frame_phase_seconds_count", (("phase", "render"),))] = 10.0
    run = {"window_s": 20.0, "workers": 1, "scrapes": {"master": ([{}], [{}]), "workers": ([at_start], [later])}}
    for name in NAMES:
        if name == "host_cpu_system_share":
            continue  # no CPU used between a scrape and itself: no share of it
        assert readers.read_metric(name, run, ROOT) == 0.0, name
