#!/usr/bin/env python3
"""The controls of cell `svc2fam-4w-kill1`, in ONE whole run of the cell
through the benchmark's own driver and checks:

    chiprun --chips 4 -- python scripts/check-failover-controls.py [--seed N] [--seconds S]
    JAX_PLATFORMS=cpu python scripts/check-failover-controls.py --rehearse --seconds 20

- a master that drops an evicted unit: the master is started through a
  shim that makes `ClusterManagerState.return_frame_to_pending` forget the
  FIRST unit it is handed with the cause `eviction` (the unit stays with the
  dead worker for ever, and nothing is reported of it). Its job was in hand
  at the kill and never finishes: the run has to fail by
  `no_unit_lost_to_a_dead_worker`, naming the job and the frame that has no
  file;
- a forged second render without a report: before the survivors' records
  are laid over the master's (`benchmark/reference/plain_failover.py::account`),
  one unit that one survivor rendered once and the master reports nothing
  of is written into another survivor's record too; the run has to fail by
  that unit, named as rendered twice with no cause.

Prints the run's lines; the last is `{"control", "correct", "problems",
"forged", "dropped"}`; exits 0 when both controls were caught, 1 when
either passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.drivers import service_kill  # noqa: E402
from benchmark.lib import launch, manifest  # noqa: E402
from benchmark.reference import plain_failover  # noqa: E402

# The master with one evicted unit dropped: what it forgot goes to its log.
FORGETFUL_MASTER = """
import sys
from tpu_render_cluster.master import main, state

back = state.ClusterManagerState.return_frame_to_pending
dropped = []

def forget_the_first_evicted(self, unit, cause):
    if cause == "eviction" and not dropped:
        dropped.append(unit)
        print(f"CONTROL: dropped evicted unit {self.job.job_name} frame {self._as_unit(unit).frame_index}", flush=True)
        return
    back(self, unit, cause)

state.ClusterManagerState.return_frame_to_pending = forget_the_first_evicted
sys.exit(main.main(sys.argv[1:]))
"""


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=4900090909)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    started_at = time.time()

    spawn = launch.Processes.spawn
    master_log: list[Path] = []

    def with_a_forgetful_master(self, argv, log, env, cwd):
        if "tpu_render_cluster.master.main" in argv:
            at = argv.index("tpu_render_cluster.master.main")
            argv = [argv[0], "-c", FORGETFUL_MASTER, *argv[at + 1:]]
            master_log.append(log)
        return spawn(self, argv, log, env, cwd)

    account = plain_failover.account
    forged: list = []

    def with_a_hand_made_pair(jobs, kill, settle_s, survivors, results, handbacks):
        stated = {(report["job_name"], int(report["frame"])) for report in handbacks}
        first, second, *_ = sorted(survivors)
        elsewhere = {(job, frame) for name, spans in survivors.items() if name != first for job, frame, _ in spans}
        of_the_dead = {(r["job_name"], int(r["frame"])) for r in results if r["worker"] == kill["worker"]}
        job, frame, ended_at = next(
            span for span in survivors[first]
            if span[:2] not in stated and span[:2] not in elsewhere and span[:2] not in of_the_dead
        )
        forged.append((job, frame))
        return account(
            jobs, kill, settle_s, {**survivors, second: survivors[second] + [(job, frame, ended_at)]}, results, handbacks,
        )

    launch.Processes.spawn = with_a_forgetful_master
    plain_failover.account = with_a_hand_made_pair
    problems: list[str] = []
    dropped: list[str] = []
    say = service_kill.say

    def keep_the_check(stage, **fields):
        if stage == "check":
            problems.extend(fields["problems"])
        if stage == "stopped" and master_log:  # the run's directory goes with the run
            dropped.extend(
                line.removeprefix("CONTROL: dropped evicted unit ")
                for line in master_log[0].read_text(errors="replace").splitlines() if line.startswith("CONTROL: ")
            )
        say(stage, **fields)

    service_kill.say = keep_the_check
    result = service_kill.run(
        manifest.load_cell("svc2fam-4w-kill1"), seed=args.seed, seconds=args.seconds,
        trace=False, started_at=started_at, rehearse=args.rehearse,
    )
    print(json.dumps({
        "control": "a master that drops one evicted unit, and one hand-made duplicate",
        "correct": result["correct"], "problems": problems, "forged": forged, "dropped": dropped,
    }), flush=True)
    caught_drop = False
    if dropped:
        job, _, frame = dropped[0].rpartition(" frame ")
        # the frame is named where it has no file (the dead worker may have
        # written it and died before it could say so)
        caught_drop = any(
            problem.startswith(f"{job} was in hand at the kill and was reported finished never")
            and (f"frames [{frame}]" in problem or "frames none" in problem) for problem in problems
        )
    caught_pair = bool(forged) and any(
        problem.startswith(f"{forged[0][0]} frame {forged[0][1]} was rendered 2 times") and "no cause" in problem
        for problem in problems
    )
    return 0 if not result["correct"] and caught_drop and caught_pair else 1


if __name__ == "__main__":
    sys.exit(main())
