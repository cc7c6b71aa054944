#!/usr/bin/env python3
"""The controls of cell `svc2fam-4w-closed12`, in ONE whole run of the
cell through the benchmark's own driver and checks:

    chiprun --chips 4 -- python scripts/check-pool-controls.py <family> <samples> [--worker I] [--seed N] [--seconds S]
    JAX_PLATFORMS=cpu python scripts/check-pool-controls.py 03_physics-2-scan 8 --rehearse
    JAX_PLATFORMS=cpu python scripts/check-pool-controls.py 04_very-simple 1 --worker 2 --rehearse

- the shape control, as `check-service-shape-control.py` has it for the
  one-worker cell: `<family>`'s jobs are submitted without their `[render]`
  table and every worker is started with `--renderSamples <samples>`, so
  the pool renders that family at the workers' flag and not at its jobs'
  shape; the run has to fail by that family's same-stream share;
- with `--worker I`, ONE worker at fault: the family's jobs go without
  their table as above, worker I alone is started with `--renderSamples
  <samples>` and the others with the family's own samples on the flag, so
  three workers render the family as its jobs would state it and one does
  not; the run has to fail by the same-stream share of a frame of that
  ONE worker (the check of one frame a family of every worker), whoever
  rendered the family's own checked frame;
- the hand-made duplicate: before the workers' records are laid over the
  master's reports (`benchmark/reference/plain_pool.py::account`), one unit
  that one worker rendered once and the master reports nothing of is
  written into another worker's record too; the run has to fail by that
  unit, named as rendered twice with no cause.

Prints the run's lines; the last is `{"control", "correct", "problems",
"forged"}`; exits 0 when the controls were caught, 1 when either passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.drivers import service_pool  # noqa: E402
from benchmark.lib import launch, manifest  # noqa: E402
from benchmark.reference import plain_pool  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("family")
    parser.add_argument("samples", type=int)
    parser.add_argument("--worker", type=int, default=None)
    parser.add_argument("--seed", type=int, default=4300090909)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    started_at = time.time()

    stream = service_pool.job_stream

    def without_the_table(cell, families):
        for job in stream(cell, families):
            if job.family.name == args.family:
                del job.spec["job"]["render"]
            yield job

    spawn = launch.Processes.spawn

    cell = manifest.load_cell("svc2fam-4w-closed12")
    (family,) = [f for f in service_pool.load_families(cell, args.seed, args.rehearse) if f.name == args.family]

    def with_the_workers_flag(self, argv, log, env, cwd):
        if any(part.endswith("worker_entry.py") for part in argv):
            at_fault = args.worker is None or int(argv[argv.index("--bench-index") + 1]) == args.worker
            argv = [*argv, "--renderSamples", str(args.samples if at_fault else family.shape["samples"])]
            if args.rehearse:
                argv += ["--renderSize", f"{family.shape['width']}x{family.shape['height']}"]
        return spawn(self, argv, log, env, cwd)

    account = plain_pool.account
    forged: list = []

    def with_a_hand_made_pair(rendered, reported):
        stated = {(report["job_name"], int(report["frame"])) for report in reported}
        first, second, *_ = sorted(rendered)
        elsewhere = {unit for name, units in rendered.items() if name != first for unit in units}
        unit = next(unit for unit in rendered[first] if unit not in stated and unit not in elsewhere)
        forged.append(unit)
        return account({**rendered, second: rendered[second] + [unit]}, reported)

    service_pool.job_stream = without_the_table
    launch.Processes.spawn = with_the_workers_flag
    plain_pool.account = with_a_hand_made_pair
    problems: list[str] = []
    say = service_pool.say

    def keep_the_check(stage, **fields):
        if stage == "check":
            problems.extend(fields["problems"])
        say(stage, **fields)

    service_pool.say = keep_the_check
    result = service_pool.run(
        cell, seed=args.seed, seconds=args.seconds,
        trace=False, started_at=started_at, rehearse=args.rehearse,
    )
    who = "the workers'" if args.worker is None else f"worker {args.worker}'s"
    print(json.dumps({
        "control": f"{args.family} at {who} {args.samples} spp, and one hand-made duplicate",
        "correct": result["correct"], "problems": problems, "forged": forged,
    }), flush=True)
    by_share = [problem for problem in problems if problem.startswith(f"{args.family}: ") and "same-stream" in problem]
    of_a_worker = {problem.split(": ")[1] for problem in by_share if problem.split(": ")[1].startswith("worker-")}
    if args.worker is None:
        caught_shape = any(problem.startswith(f"{args.family}: same-stream") for problem in by_share)
    else:  # one worker's frame, and nobody else's
        caught_shape = len(of_a_worker) == 1
    caught_pair = bool(forged) and any(
        problem.startswith(f"{forged[0][0]} frame {forged[0][1]} was rendered 2 times") and "no cause" in problem
        for problem in problems
    )
    return 0 if not result["correct"] and caught_shape and caught_pair else 1


if __name__ == "__main__":
    sys.exit(main())
