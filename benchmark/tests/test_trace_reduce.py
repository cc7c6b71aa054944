"""The trace reducer on a small synthetic trace: two frames, each a loop
holding two kernel calls and a sort, then a copy after the loop."""

import json

import pytest

from benchmark.lib import trace_reduce as tr

WHILE = "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple), condition=%c, body=%b"
KERNEL = '%_mesh_step.7 = f32[3,64]{1,0} custom-call(f32[3]{0} %x), custom_call_target="tpu_custom_call"'
SORT = "%sort.3 = s32[64]{0} sort(s32[64]{0} %keys), dimensions={0}"
COPY = "%copy.2 = f32[64,3]{1,0} copy(f32[64,3]{0,1} %image)"
OPS = [
    (WHILE, 1.00, 1.50), (KERNEL, 1.02, 1.20), (SORT, 1.20, 1.25), (KERNEL, 1.30, 1.48), (COPY, 1.55, 1.60),
    (WHILE, 2.00, 2.50), (KERNEL, 2.02, 2.20), (SORT, 2.20, 2.25), (KERNEL, 2.30, 2.48), (COPY, 2.55, 2.60),
]
LEAVES = [op for op in OPS if op[0] != WHILE]
SPANS = [("render", 0.95, 1.70), ("write", 1.70, 1.90), ("render", 1.95, 2.70), ("write", 2.70, 2.90)]


def test_busy_union_merges_nested_and_overlapping():
    assert tr.busy_union(OPS) == [(1.00, 1.50), (1.55, 1.60), (2.00, 2.50), (2.55, 2.60)]
    assert tr.busy_seconds(OPS) == pytest.approx(1.10)


def test_kernel_seconds_sums_matching_operations_once():
    assert tr.kernel_seconds(OPS, r"tpu_custom_call") == pytest.approx(0.72)
    doubled = OPS + [(KERNEL.replace("_mesh_step.7", "wrapper"), 1.02, 1.20)]  # one kernel under two names
    assert tr.kernel_seconds(doubled, r"tpu_custom_call") == pytest.approx(0.72)


def test_top_operations_leave_out_envelopes_and_keep_overlapping_work():
    overlapping = OPS + [(COPY, 1.05, 1.10)]  # an async copy under a kernel
    top = dict(tr.top_operations(overlapping))
    assert not any("while" in name for name in top)  # the loop is its body's envelope
    assert top["%_mesh_step.7 custom-call f32[3,64]"] == pytest.approx(0.72)
    assert top["%sort.3 sort s32[64]"] == pytest.approx(0.10)
    assert top["%copy.2 copy f32[64,3]"] == pytest.approx(0.15)


def test_idle_gaps_complement_the_busy_union():
    gaps = tr.idle_gaps(OPS, 0.9, 3.0)
    assert sum(b - a for a, b in gaps) + tr.busy_seconds(tr.clip(OPS, 0.9, 3.0)) == pytest.approx(2.1)


def test_gaps_are_labelled_by_what_the_host_was_doing():
    # without the loop's envelope, the gaps inside it show
    labels = dict(tr.label_gaps(tr.idle_gaps(LEAVES, 0.9, 3.0), SPANS, LEAVES))
    assert labels["render:dispatch"] == pytest.approx(2 * 0.07)
    assert labels["render:host_between_ops"] == pytest.approx(2 * (0.05 + 0.07))
    assert labels["render:readback"] == pytest.approx(2 * 0.10)
    assert labels["write"] == pytest.approx(0.40)
    assert labels["between_frames"] == pytest.approx(0.05 + 0.05 + 0.10)


def test_to_wall_and_clip_move_and_cut_intervals():
    moved = tr.to_wall([("k", 0.5, 0.7)], mark_s=0.1, mark_wall_s=1000.1)
    assert moved == [("k", pytest.approx(1000.5), pytest.approx(1000.7))]
    assert tr.clip(moved, 1000.6, 2000.0) == [("k", 1000.6, pytest.approx(1000.7))]


def test_covered_seconds_finds_the_worker_whose_spans_hold_the_device_work():
    busy = tr.busy_union(OPS)
    own = [s for s in SPANS if s[0] == "render"]
    other = [("render", 1.60, 2.05), ("render", 2.58, 3.0)]
    assert tr.covered_seconds(own, busy) == pytest.approx(1.10)
    assert tr.covered_seconds(other, busy) < 0.2


def test_device_operations_prefer_the_ops_line_and_phase_spans_parse(tmp_path):
    device = {"lines": [
        {"name": "XLA Modules", "events": [["jit_render", 1.0, 2.6]]},
        {"name": "XLA Ops", "events": [[n, a, b] for n, a, b in OPS]},
    ]}
    assert len(tr.device_operations(device)) == len(OPS)
    path = tmp_path / "worker-x_trace-events.json"
    path.write_text(json.dumps({"traceEvents": [
        {"name": "render", "cat": "worker", "ph": "X", "ts": 1.95e6, "dur": 0.75e6},
        {"name": "queue_wait", "cat": "worker", "ph": "X", "ts": 0.0, "dur": 1e6},
        {"name": "frame", "cat": "frame", "ph": "t", "ts": 2e6},
    ]}))
    assert tr.worker_phase_spans(path) == [("render", pytest.approx(1.95), pytest.approx(2.70))]


@pytest.mark.parametrize("name,short", [
    ("%fusion.3 = s32[2097152]{0:T(1024)} fusion(s32[2097152]{0:T(1024)} %rng.1), kind=kCustom, calls=%fused_computation.3",
     "%fusion.3 fusion s32[2097152]"),
    ('%_mesh_step.1 = (f32[3,2097152]{1,0:T(4,128)S(1)}, s32[1,2097152]{1,0}) custom-call(f32[3]{0} %x), custom_call_target="tpu_custom_call"',
     "%_mesh_step.1 custom-call f32[3,2097152]"),
    ("copy.2", "copy.2"),
])
def test_hlo_text_is_shortened_to_name_opcode_and_shape(name, short):
    assert tr.short_name(name)[0] == short
