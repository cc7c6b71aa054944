"""Line-JSON assignment service for the C++ master daemon (trc-master).

Keeps the tpu-batch scheduler's *math* in JAX (on the host CPU: the chips
belong to the workers) while the control plane is native: the C++ master
(native/master_daemon.cpp) launches this module as a persistent subprocess
and streams one JSON object per line on stdin, receiving one per line on
stdout:

    -> {"id": N, "cost": [[...], ...]}            an [items, slots] cost matrix
    <- {"id": N, "assignment": [s0, s1, ...]}     slot index per item
    -> {"op": "exit"}                             clean shutdown

Requests carry an ``id`` echoed back in the response so a caller that timed
out on one solve can discard the stale line instead of mis-pairing it with
the next request (the same correlation idea as the wire protocol's
``message_request_context_id``).

On startup the service warms the auction solver across the power-of-two
shape buckets real clusters hit (XLA compiles once per bucket) and then
prints ``{"ready": true}``; until that line arrives the C++ side uses its greedy host fallback,
mirroring how tpu_render_cluster/master/tpu_batch.py degrades.

This replaces the reference's in-process scheduler math (reference:
master/src/cluster/strategies.rs:16-405) with an out-of-process JAX solve;
only frame->worker assignments travel back over the pipe (SURVEY.md §5.8).
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    from tpu_render_cluster.utils.accelerator import (
        configure_compile_cache,
        pin_jax_to_host_cpu,
    )

    pin_jax_to_host_cpu()
    configure_compile_cache()

    import numpy as np

    from tpu_render_cluster.ops.assignment import (
        greedy_fallback_count,
        reset_greedy_fallback_count,
        solve_assignment,
    )

    # Warm the solver across shape buckets so scheduling ticks never absorb
    # an XLA compile: solve_assignment pads to square power-of-two buckets
    # (ops/assignment.py _next_bucket), so one solve per bucket caches the
    # compiled kernel. 8..128 covers up to 128 simultaneous queue slots.
    for bucket in (8, 16, 32, 64, 128):
        warmup = np.ones((bucket // 2, bucket), dtype=np.float32)
        solve_assignment(warmup)
    # Warmup solves don't count toward the job's fallback telemetry.
    reset_greedy_fallback_count()
    sys.stdout.write(json.dumps({"ready": True}) + "\n")
    sys.stdout.flush()

    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            request = json.loads(line)
        except json.JSONDecodeError:
            sys.stdout.write(json.dumps({"error": "malformed request"}) + "\n")
            sys.stdout.flush()
            continue
        if request.get("op") == "exit":
            break
        request_id = request.get("id")
        cost = np.asarray(request.get("cost", []), dtype=np.float32)
        if cost.ndim != 2 or cost.size == 0:
            sys.stdout.write(json.dumps({"id": request_id, "assignment": []}) + "\n")
            sys.stdout.flush()
            continue
        assignment = solve_assignment(cost)
        # Cumulative non-convergence fallback count rides every response so
        # the C++ master can surface it in its processed-results scheduler
        # section without an extra request.
        sys.stdout.write(
            json.dumps(
                {
                    "id": request_id,
                    "assignment": [int(s) for s in assignment],
                    "greedy_fallbacks": greedy_fallback_count(),
                }
            )
            + "\n"
        )
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
