#!/usr/bin/env bash
# Run a full job locally: one master + N workers on localhost.
#
# The local multi-process harness the reference never scripted (SURVEY.md §4.4).
#
# Usage:
#   scripts/run-local-cluster.sh <job.toml> <n_workers> [backend] [results_dir]
#
#   backend: mock | tpu-raytrace | blender   (default: mock)
#
# The master runs on the host CPU (it pins itself). With tpu-raytrace,
# worker i is confined to local chip i-1 — a chip belongs to one process,
# so n_workers must not exceed the chips present.
set -euo pipefail

JOB_FILE="${1:?usage: run-local-cluster.sh <job.toml> <n_workers> [backend] [results_dir]}"
N_WORKERS="${2:?need worker count}"
BACKEND="${3:-mock}"
RESULTS_DIR="${4:-./results}"
PORT="${TRC_PORT:-9901}"
BASE_DIR="${TRC_BASE_DIR:-$(pwd)}"

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_ROOT}${PYTHONPATH:+:$PYTHONPATH}"

mkdir -p "$RESULTS_DIR"

python -m tpu_render_cluster.master.main \
  --host 127.0.0.1 --port "$PORT" \
  run-job "$JOB_FILE" --resultsDirectory "$RESULTS_DIR" &
MASTER_PID=$!

cleanup() { kill "$MASTER_PID" ${WORKER_PIDS:-} 2>/dev/null || true; }
trap cleanup EXIT

sleep 1
WORKER_PIDS=""
for i in $(seq 1 "$N_WORKERS"); do
  CHIP_ENV=""
  if [ "$BACKEND" = "tpu-raytrace" ]; then
    CHIP_ENV="$(python -m tpu_render_cluster.utils.accelerator "$((i - 1))")"
  fi
  # shellcheck disable=SC2086  # CHIP_ENV is a list of KEY=VALUE words
  env $CHIP_ENV python -m tpu_render_cluster.worker.main \
    --masterServerHost 127.0.0.1 --masterServerPort "$PORT" \
    --baseDirectory "$BASE_DIR" --backend "$BACKEND" &
  WORKER_PIDS="$WORKER_PIDS $!"
  sleep 0.2   # staggered starts, like the reference SLURM scripts
done

wait "$MASTER_PID"
MASTER_RC=$?
wait $WORKER_PIDS 2>/dev/null || true
trap - EXIT
exit "$MASTER_RC"
