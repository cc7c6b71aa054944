"""`benchmark/reference/plain_failover.py` on forged runs: the sound one
passes, and each of six others holds one fault, which the reference names.

The forged run: four jobs of four frames, worker `dead` killed at t = 100
with `b` frame 2 on its device and `b` frame 3 queued, evicted at 130, both
re-rendered by `w1` and `w2`; `a` had finished before the kill (the dead
worker delivered `a` frame 1), `b` and `c` were in hand, `d` came after.
"""

from __future__ import annotations

import copy
import json

import pytest

from benchmark.reference import plain_failover

KILL = {"worker": "dead", "at": 100.0}
SETTLE_S = 40.0


def sound_run() -> dict:
    def job(name, submitted_at, finished_at, files):
        return {"name": name, "first": 1, "last": 4, "submitted_at": submitted_at, "finished_at": finished_at,
                "files": files, "other_paths": []}
    return {
        "jobs": [
            job("a", 80.0, 95.0, {1: 90.0, 2: 91.0, 3: 92.0, 4: 93.0}),
            job("b", 90.0, 131.5, {1: 99.0, 2: 131.0, 3: 131.2, 4: 101.0}),
            job("c", 92.0, 104.0, {1: 101.0, 2: 102.0, 3: 102.5, 4: 103.0}),
            job("d", 105.0, None, {1: 110.0}),
        ],
        "survivors": {
            "w1": [("a", 2, 91.0), ("b", 1, 99.0), ("b", 2, 131.0), ("c", 1, 101.0), ("c", 3, 102.5), ("d", 1, 110.0)],
            "w2": [("a", 3, 92.0), ("a", 4, 93.0), ("b", 3, 131.2), ("b", 4, 101.0), ("c", 2, 102.0), ("c", 4, 103.0)],
        },
        "results": [
            {"job_name": "a", "frame": 1, "worker": "dead"}, {"job_name": "a", "frame": 2, "worker": "w1"},
            {"job_name": "b", "frame": 2, "worker": "w1"}, {"job_name": "b", "frame": 3, "worker": "w2"},
        ],
        "handbacks": [
            {"job_name": "b", "frame": 2, "worker": "dead", "cause": "eviction", "at": 130.0},
            {"job_name": "b", "frame": 3, "worker": "dead", "cause": "eviction", "at": 130.0},
            {"job_name": "c", "frame": 4, "worker": "w1", "cause": "preemption", "at": 101.5},
        ],
    }


def faults(run: dict) -> list[str]:
    accounted = plain_failover.account(
        run["jobs"], KILL, SETTLE_S, run["survivors"], run["results"], run["handbacks"]
    )
    return plain_failover.problems(accounted, KILL, SETTLE_S)


def never_rendered_again(run):
    run["survivors"]["w2"].remove(("b", 3, 131.2))
    return "b frame 3 was with the dead worker dead and no survivor rendered it after the kill"


def a_second_render_without_a_report(run):
    run["survivors"]["w2"].append(("c", 1, 103.5))
    return "c frame 1 was rendered 2 times (w1, w2) and the master reports no cause"


def a_cause_the_guarantee_does_not_name(run):
    run["handbacks"].append({"job_name": "c", "frame": 2, "worker": "w2", "cause": "impatience", "at": 102.0})
    return "c frame 2 left a worker for 'impatience', a cause the guarantee does not name"


def a_temporary_file_in_a_finished_jobs_directory(run):
    run["jobs"][1]["other_paths"] = [".rendered-000002.jpg.x7k2.tmp"]
    return "b was reported finished and its directory holds .rendered-000002.jpg.x7k2.tmp, no frame of it"


def a_job_in_hand_not_finished_inside_settle(run):
    run["jobs"][1]["finished_at"] = None
    del run["jobs"][1]["files"][4]
    return "b was in hand at the kill and was reported finished never, not inside 40 s of it; of its range, frames [4]"


def a_job_in_hand_finished_too_late(run):
    run["jobs"][1]["finished_at"] = 141.0
    return "b was in hand at the kill and was reported finished 41.0 s after the kill, not inside 40 s of it"


def test_the_sound_run_has_no_fault_and_its_account_reads_as_it_happened():
    run = sound_run()
    assert faults(run) == []
    accounted = plain_failover.account(
        run["jobs"], KILL, SETTLE_S, run["survivors"], run["results"], run["handbacks"]
    )
    assert accounted["in_hand"] == ["b", "c"]
    assert [(u["job"], u["frame"], u["back_at"], u["rendered_again_at"], u["file_at"]) for u in accounted["stranded"]] == [
        ("b", 2, 130.0, 131.0, 131.0), ("b", 3, 130.0, 131.2, 131.2),
    ]
    assert accounted["rendered_twice"] == {"explained": [], "unexplained": []}
    assert accounted["late"] == accounted["leavings"] == accounted["bad_causes"] == []


def test_a_render_the_master_took_from_the_dead_worker_counts_as_one_of_two():
    """The dead worker left no timeline: its share is the master's record."""
    run = sound_run()
    run["results"].append({"job_name": "c", "frame": 1, "worker": "dead"})
    (fault,) = faults(run)
    assert fault.startswith("c frame 1 was rendered 2 times (dead (dead), w1) and the master reports no cause")
    run["handbacks"].append({"job_name": "c", "frame": 1, "worker": "dead", "cause": "steal", "at": 100.5})
    assert faults(run) == []


@pytest.mark.parametrize("forge", [
    never_rendered_again, a_second_render_without_a_report, a_cause_the_guarantee_does_not_name,
    a_temporary_file_in_a_finished_jobs_directory, a_job_in_hand_not_finished_inside_settle,
    a_job_in_hand_finished_too_late,
], ids=lambda forge: forge.__name__)
def test_one_fault_is_named_and_nothing_else_is(forge):
    run = copy.deepcopy(sound_run())
    names = forge(run)
    found = faults(run)
    assert len(found) == 1 and found[0].startswith(names), found


@pytest.mark.parametrize("events, want", [
    ([{"ph": "X", "cat": "worker", "name": "render", "ts": 5e6, "dur": 2e6, "args": {"frame": 3, "job": "a"}},
      {"ph": "X", "cat": "worker", "name": "write", "ts": 7e6, "dur": 1e6, "args": {"frame": 3, "job": "a"}}],
     [("a", 3, 7.0)]),
    ([{"ph": "X", "cat": "worker", "name": "render", "ts": 5e6, "dur": 2e6, "args": {"frame": 3}}], None),
    ([], []),
], ids=["a render span", "a program that names no job", "no span"])
def test_a_survivors_record_is_read_from_its_render_spans(tmp_path, events, want):
    path = tmp_path / "worker-x_trace-events.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert plain_failover.rendered_spans(path) == want
