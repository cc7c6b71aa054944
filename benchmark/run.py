#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --list

One run of one cell of `BENCHMARK.json` on the machine this is started on.
Detail goes out as one JSON line per stage; the last line of standard
output is the result: `correct`, `attempted`, `failed`, `metrics`, `device`
(and `breakdown` in a traced run). Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

`--rehearse` walks the same path on the CPU at 64x64 to find faults in
paths, arguments and control flow before chip time is spent; its line
says `"platform": "cpu"`, and none of its numbers is a measurement.
"""

from __future__ import annotations

import time

STARTED_AT = time.time()  # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.launch import BenchFailure  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--list", action="store_true", help="print every metric and cell, and exit")
    parser.add_argument("--rehearse", action="store_true", help="CPU walk-through at 64x64; not a measurement")
    args = parser.parse_args(argv)
    try:
        if args.list:
            print(manifest.listing())
            problems = manifest.validate()
            for problem in problems:
                print(f"benchmark: {problem}", file=sys.stderr)
            return 1 if problems else 0
        if not args.workload:
            parser.error("--workload is required")
        if not (ROOT / "tpu_render_cluster").is_dir():
            raise BenchFailure("the system under test (tpu_render_cluster/) is not in this checkout")
        cell = manifest.load_cell(args.workload)
        seconds = args.seconds if args.seconds is not None else manifest.load_benchmark()["run_seconds"]
        driver = importlib.import_module(f"benchmark.drivers.{cell.traffic['driver']}")
        result = driver.run(
            cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
            started_at=STARTED_AT, rehearse=args.rehearse,
        )
    except (BenchFailure, manifest.ManifestError) as failure:
        print(f"benchmark: FAILED: {failure}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
