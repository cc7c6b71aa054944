#!/usr/bin/env python3
"""The shape control of cell `svc2fam-1w-closed3`: one whole run of the cell
in which ONE family's jobs do not state their shape, so the worker renders
them at its own flags, through the benchmark's own driver and check.

    chiprun -- python scripts/check-service-shape-control.py <family> <samples> [--seed N] [--seconds S]
    JAX_PLATFORMS=cpu python scripts/check-service-shape-control.py 04_very-simple 1 --rehearse

`<family>`'s jobs are submitted without their `[render]` table and the worker
is started with `--renderSamples <samples>`: `03_physics-2-scan 8` serves
the asset shot at the worker's default 8 spp where its jobs state 1,
`04_very-simple 1` the previews at 1 where they state 8. The other family
states its shape as always. The run has to come out `correct: false`, by
that family's same-stream share: the control that a job's shape is honoured
frame by frame. Prints the run's lines; the last is `{"control", "correct",
"problems"}`; exits 0 when the control was caught, 1 when it passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.drivers import service  # noqa: E402
from benchmark.lib import launch, manifest  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("family")
    parser.add_argument("samples", type=int)
    parser.add_argument("--seed", type=int, default=4000090909)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    started_at = time.time()

    stream = service.job_stream

    def without_the_table(cell, families):
        for job in stream(cell, families):
            if job.family.name == args.family:
                del job.spec["job"]["render"]
            yield job

    spawn = launch.Processes.spawn

    def with_the_workers_flag(self, argv, log, env, cwd):
        if any(part.endswith("worker_entry.py") for part in argv):
            argv = [*argv, "--renderSamples", str(args.samples)]
            if args.rehearse:
                argv += ["--renderSize", "64x64"]
        return spawn(self, argv, log, env, cwd)

    service.job_stream = without_the_table
    launch.Processes.spawn = with_the_workers_flag
    problems: list[str] = []
    say = service.say

    def keep_the_check(stage, **fields):
        if stage == "check":
            problems.extend(fields["problems"])
        say(stage, **fields)

    service.say = keep_the_check
    result = service.run(
        manifest.load_cell("svc2fam-1w-closed3"), seed=args.seed, seconds=args.seconds,
        trace=False, started_at=started_at, rehearse=args.rehearse,
    )
    print(json.dumps({
        "control": f"{args.family} at the worker's {args.samples} spp", "correct": result["correct"],
        "problems": problems,
    }), flush=True)
    caught = not result["correct"] and any(
        problem.startswith(f"{args.family}: same-stream") for problem in problems
    )
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
