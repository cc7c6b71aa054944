"""``TRC_*`` environment overrides for runtime tuning knobs — the registry.

The transport deadlines, retry caps, and heartbeat tolerances all ship
reference-derived defaults but are consulted through these helpers so a
deployment (or the chaos harness, which compresses every timeout to keep
fault scenarios fast) can retune them without code changes. Values are
read at *call* time, not import time: long-lived processes and tests that
monkeypatch ``os.environ`` both see the current value.

This module is also the single place a ``TRC_*`` variable may touch
``os.environ``, and the single place every variable is DECLARED: the
``env-registry`` lint pass (``tpu_render_cluster/lint/env_registry.py``)
refuses direct ``os.environ`` reads of ``TRC_*`` names elsewhere in the
package, refuses helper reads of names missing from :data:`ENV_VARS`,
and cross-checks the registry against README.md's environment tables —
an undeclared read, a double declaration, a dead declaration, and a
missing README row are all tier-1 failures.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Declarations


@dataclass(frozen=True)
class EnvVar:
    """One declared ``TRC_*`` knob (name, value grammar, one-line doc)."""

    name: str
    kind: str  # "int" | "float" | "str" | "flag" | "path" | "port" | "spec"
    default: object
    doc: str


ENV_VARS: dict[str, EnvVar] = {}


def declare(name: str, kind: str, default: object, doc: str) -> None:
    """Register one variable; a duplicate declaration is a programming
    error (and an ``env-registry`` lint finding) rather than a silent
    overwrite."""
    if name in ENV_VARS:
        raise ValueError(f"duplicate env declaration: {name}")
    ENV_VARS[name] = EnvVar(name, kind, default, doc)


# -- transport / reconnect ---------------------------------------------------
declare("TRC_BACKOFF_BASE", "float", 2.0, "Full-jitter reconnect backoff base")
declare("TRC_BACKOFF_CAP_SECONDS", "float", 30.0, "Reconnect backoff sleep cap")
declare("TRC_MAX_CONNECT_RETRIES", "int", 12, "Connect attempts before giving up")
declare("TRC_MAX_RECONNECTS_PER_OP", "int", 2, "Reconnects one logical op may absorb")
declare("TRC_OP_DEADLINE_SECONDS", "float", 30.0, "Per-op reconnect deadline")
declare("TRC_SEND_DEADLINE_SECONDS", "float", 45.0, "Master->worker send deadline")
declare("TRC_RPC_DEADLINE_SECONDS", "float", 60.0, "Master->worker ack deadline")
declare("TRC_HEARTBEAT_PONG_RETRIES", "int", 1, "Extra pings after a missed pong")
# -- master / units ----------------------------------------------------------
declare("TRC_MAX_UNIT_ERRORS", "int", 8, "Deterministic render errors per unit before the job fails")
# -- render tiers ------------------------------------------------------------
declare("TRC_PALLAS", "flag", None, "Pallas kernel dispatch override (1/0; unset = TPU only)")
declare("TRC_TLAS", "flag", 1, "Two-level (TLAS) mesh traversal on/off")
declare("TRC_TLAS_LEAF", "int", 4, "Instances per TLAS leaf (clamped 1..16)")
declare("TRC_TLAS_BLOCK", "int", 256, "Ray-block width of the TLAS kernel variants")
declare("TRC_BVH_QUANT", "int", 0, "Quantized BVH/TLAS node tier: 0 off, 1 16-bit, 2 8-bit slabs")
declare("TRC_BVH_BUILDER", "spec", "sah", "BLAS build strategy: sah (binned) | median")
declare("TRC_BVH_WIDE", "int", 4, "BLAS branching factor after wide collapse (1 = binary, clamped 1..8)")
# -- jobs / tiles ------------------------------------------------------------
declare("TRC_TILE_GRID", "spec", None, "Default RxC tile grid applied at job load time")
# -- logging / analysis paths ------------------------------------------------
declare("TRC_LOG", "spec", None, "Log level/filter (RUST_LOG grammar; RUST_LOG also accepted)")
declare("TRC_RESULTS_ROOT", "path", None, "Root for experiment results")
declare("TRC_RESULTS_DIR", "path", None, "Cluster-run trace directory")
declare("TRC_ANALYSIS_DIR", "path", None, "Analysis output directory")
# -- chaos -------------------------------------------------------------------
declare("TRC_CHAOS_SEED", "int", 0, "Default fault-plan seed for FaultPlan.from_env()")
declare("TRC_CHAOS_WORKERS", "int", 3, "Default fault-plan worker count")
declare("TRC_CHAOS_PLAN", "path", None, "Fault-plan TOML path (wins over seed/workers)")
# -- scheduler ---------------------------------------------------------------
declare("TRC_SCHED_TICK_SECONDS", "float", 0.05, "Scheduler dispatch/admission tick")
declare("TRC_SCHED_TARGET_QUEUE_SIZE", "int", 2, "In-flight slots per live worker")
declare("TRC_SCHED_MAX_ACTIVE_JOBS", "int", 4, "Concurrently running jobs")
declare("TRC_SCHED_PREEMPTION", "flag", 1, "Preemption of over-share jobs on/off")
declare("TRC_SCHED_MAX_PREEMPTIONS_PER_TICK", "int", 1, "Preemptions per scheduler tick")
declare("TRC_SCHED_DRAIN_GRACE_SECONDS", "float", 10.0, "Drain grace before cancelling barrier-unadmittable jobs")
declare("TRC_SCHED_TICK", "spec", "heap", "Tick pick structure: heap | scan (legacy full rescan) | verify (heap + scan cross-check)")
declare("TRC_DISPATCH_FRAMES", "spec", "cached", "Dispatch frame encoding: cached (preserialized splice) | encode (per-send JSON)")
# -- cost model / speculation ------------------------------------------------
declare("TRC_COST_MODEL", "path", None, "Trace-trained cost model loaded at master start")
declare("TRC_SPECULATION", "flag", 0, "Straggler-aware speculative re-execution on/off")
declare("TRC_SPEC_THRESHOLD", "float", 2.0, "Tail-score multiple of p50 that triggers a hedge")
declare("TRC_SPEC_MIN_SAMPLES", "int", 3, "Cost-model observations before prediction-triggered hedging")
declare("TRC_SPEC_MAX_ACTIVE", "int", 2, "Concurrent speculative twins per job")
# -- telemetry / SLO ---------------------------------------------------------
declare("TRC_OBS_PORT", "port", None, "Master /metrics + /healthz + /clusterz port")
declare("TRC_OBS_WORKER_PORT", "port", None, "Worker /metrics + /healthz port")
declare("TRC_OBS_ROUTER_PORT", "port", None, "Shard router federated telemetry port")
declare("TRC_SLO_SHORT_WINDOW_SECONDS", "float", 60.0, "SLO burn short window")
declare("TRC_SLO_LONG_WINDOW_SECONDS", "float", 300.0, "SLO burn long window")
declare("TRC_SLO_BURN_THRESHOLD", "float", 1.0, "Burn ratio that counts as breaching")
declare("TRC_SLO_MIN_WINDOW_SAMPLES", "int", 1, "Observations a window needs before it may breach")
declare("TRC_SLO_TICK_SECONDS", "float", 0.5, "Periodic SLO evaluation interval")
# -- continuous observability ------------------------------------------------
declare("TRC_OBS_HISTORY_INTERVAL", "float", 1.0, "Metrics-history sampling interval")
declare("TRC_OBS_HISTORY_RETENTION", "float", 600.0, "Metrics-history ring reach (seconds)")
declare("TRC_OBS_FLIGHT_SECONDS", "float", 60.0, "Flight-recorder bundle window")
declare("TRC_OBS_FLIGHT_DEBOUNCE", "float", 5.0, "Min spacing between dumps per trigger kind")
declare("TRC_OBS_FLIGHT_EVENTS", "int", 4096, "Flight-recorder protocol-digest ring size")
declare("TRC_OBS_FLIGHT_DIR", "path", None, "Blackbox bundle directory")
declare("TRC_OBS_LOOPMON_INTERVAL", "float", 0.25, "Event-loop lag probe interval")
declare("TRC_OBS_LOOPMON_THRESHOLD", "float", 0.1, "Loop lag that counts as a blocked episode")
declare("TRC_SCHED_PROFILE", "flag", 1, "Scheduler tick phase profiling on/off")
# -- replicated control plane ------------------------------------------------
declare("TRC_HA_LEDGER", "path", None, "Write-ahead job ledger directory (master --ledger default)")
declare("TRC_HA_FSYNC", "flag", 1, "fsync after every ledger append")
declare("TRC_HA_SEGMENT_RECORDS", "int", 4096, "Ledger records per segment before rotation")
declare("TRC_HA_SNAPSHOT_EVERY", "int", 8192, "Appends between automatic ledger snapshots (0 off)")
declare("TRC_HA_REPL_PORT", "port", None, "Ledger streaming-replication listen port (master --replicationPort default)")
declare("TRC_HA_REPL_ACK_EVERY", "int", 32, "Applied records between follower cumulative acks")
declare("TRC_HA_REPL_RETRY_SECONDS", "float", 0.5, "Follower reconnect delay after a broken replication stream")
declare("TRC_HA_REPL_PROBE_SECONDS", "float", 0.5, "Router shard-liveness probe interval")
declare("TRC_HA_REPL_PROMOTE_TIMEOUT", "float", 2.0, "Unreachable-primary window before the router promotes a follower")
# -- live shard rebalancing ---------------------------------------------------
declare("TRC_REBALANCE", "flag", 0, "Router-driven hot->cold worker rebalancing on/off")
declare("TRC_REBALANCE_INTERVAL_SECONDS", "float", 5.0, "Rebalancer scrape/decide tick interval")
declare("TRC_REBALANCE_THRESHOLD", "float", 2.0, "Hot/cold per-worker load ratio that counts as imbalanced")
declare("TRC_REBALANCE_HYSTERESIS_TICKS", "int", 3, "Consecutive imbalanced ticks before the first move")
declare("TRC_REBALANCE_COOLDOWN_SECONDS", "float", 30.0, "Min spacing between rebalance moves")
declare("TRC_REBALANCE_MAX_MOVES", "int", 2, "Max workers migrated per rebalance move")


# ---------------------------------------------------------------------------
# Readers (consulted at call time, never cached)


def env_float(name: str, default: float) -> float:
    """``float(os.environ[name])`` with a logged fallback on bad values."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("Ignoring non-numeric %s=%r; using %s", name, raw, default)
        return default


def env_int(name: str, default: int) -> int:
    """``int(os.environ[name])`` with a logged fallback on bad values."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        logger.warning("Ignoring non-integer %s=%r; using %s", name, raw, default)
        return default


def env_str(name: str, default: str | None = None) -> str | None:
    """Raw string value, or ``default`` when unset.

    Unlike the numeric readers an empty string is returned as-is: several
    knobs (``TRC_TILE_GRID``, ``TRC_COST_MODEL``) treat ``""`` and unset
    identically by stripping at the call site, while others distinguish
    unset (``None``) from an explicit value.
    """
    raw = os.environ.get(name)
    return default if raw is None else raw
