"""Seeded, reproducible fault plans.

A ``FaultPlan`` is pure data: a tuple of ``FaultEvent``s derived from one
PCG64 stream, so the *schedule* (which worker slot suffers which fault,
when, with what parameters) is bit-identical across runs of the same seed
— re-running a failed chaos run replays the exact same faults. Runtime
interleaving naturally still varies; the invariants asserted by
``chaos/invariants.py`` are written to hold under every interleaving of a
given schedule.

Plans address workers by **slot** (their index in the harness's backend
list), not by worker id — ids are random per process. The runner maps
slots to live workers at startup.

Configuration surfaces, mirroring the repo's ``TRC_*`` idiom:

- ``FaultPlan.generate(seed, workers, ...)`` — the seeded generator;
- ``FaultPlan.from_toml(path)`` — an explicit or generated plan from TOML;
- ``FaultPlan.from_env()`` — ``TRC_CHAOS_PLAN`` (TOML path) or
  ``TRC_CHAOS_SEED``/``TRC_CHAOS_WORKERS`` for a generated default plan.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import asdict, dataclass, field, fields
from pathlib import Path
from typing import Any

import tomllib

import numpy as np
from tpu_render_cluster.utils.env import env_int, env_str

# -- fault vocabulary --------------------------------------------------------

# Transport faults (executed by transport/faults.py via chaos/inject.py).
KIND_DROP_SEND = "drop_send"
KIND_DELAY_SEND = "delay_send"
KIND_DUPLICATE_SEND = "duplicate_send"
KIND_KILL_SOCKET = "kill_socket"
KIND_PARTITION = "partition"
# Worker faults (executed by worker/backends/chaos.py + the controller).
KIND_CRASH_BEFORE_RESULT = "crash_before_result"
KIND_CRASH_AFTER_RESULT = "crash_after_result"
KIND_SLOW_RENDER = "slow_render"
KIND_HANG = "hang"
KIND_DRAIN = "drain"
# Master faults (executed by the dispatch-delay shim in worker_handle.py).
KIND_DELAY_DISPATCH = "delay_dispatch"
# Control-plane faults (executed by the failover harness, ha/chaos.py):
# the TARGET is the master, addressed by the ``MASTER_TARGET`` sentinel
# rather than a worker slot.
KIND_MASTER_KILL = "master_kill"
KIND_MASTER_PARTITION = "master_partition"
# Replication/router faults (executed by the replicated-failover harness,
# ha/chaos.py): the replication stream severed mid-flight, the shard
# router itself killed and restarted, and a follower artificially lagged
# (per-record apply delay) so promotion picks among unequal replicas.
KIND_REPLICATION_PARTITION = "replication_partition"
KIND_ROUTER_KILL = "router_kill"
KIND_FOLLOWER_LAG = "follower_lag"

# Slot sentinel for faults aimed at the master process itself.
MASTER_TARGET = -1

ALL_KINDS = (
    KIND_DROP_SEND,
    KIND_DELAY_SEND,
    KIND_DUPLICATE_SEND,
    KIND_KILL_SOCKET,
    KIND_PARTITION,
    KIND_CRASH_BEFORE_RESULT,
    KIND_CRASH_AFTER_RESULT,
    KIND_SLOW_RENDER,
    KIND_HANG,
    KIND_DRAIN,
    KIND_DELAY_DISPATCH,
    KIND_MASTER_KILL,
    KIND_MASTER_PARTITION,
    KIND_REPLICATION_PARTITION,
    KIND_ROUTER_KILL,
    KIND_FOLLOWER_LAG,
)

MASTER_KINDS = (KIND_MASTER_KILL, KIND_MASTER_PARTITION)
REPLICATION_KINDS = (
    KIND_REPLICATION_PARTITION,
    KIND_ROUTER_KILL,
    KIND_FOLLOWER_LAG,
)

FINISHED_EVENT_TYPE = "event_frame-queue_item-finished"
RENDERING_EVENT_TYPE = "event_frame-queue_item-started-rendering"


@dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault. Which fields matter depends on ``kind``:

    - time-triggered kinds (``partition``, ``drain``) fire ``at_seconds``
      after the cluster starts, ``partition`` for ``duration_seconds``;
    - send-triggered kinds (``drop/delay/duplicate_send``, ``kill_socket``)
      fire on the ``nth`` outgoing message whose wire tag equals
      ``match_message_type`` (``None`` matches every message);
      ``delay_send`` stalls that send for ``duration_seconds``;
    - render-triggered kinds (``crash_before/after_result``, ``hang``)
      fire on the ``nth`` frame that worker renders; ``slow_render``
      stretches every render by ``multiplier``;
    - ``delay_dispatch`` (master side) stalls the ``nth`` queue-add RPC to
      that slot's worker by ``duration_seconds``.

    ``causes_eviction`` is the generator's declaration that this fault is
    expected to get the worker evicted — the invariant checker compares
    ``master_worker_evictions_total`` against the plan's sum.
    """

    kind: str
    target: int
    at_seconds: float = 0.0
    duration_seconds: float = 0.0
    nth: int = 1
    multiplier: float = 1.0
    match_message_type: str | None = None
    causes_eviction: bool = False

    def __post_init__(self) -> None:
        if self.kind not in ALL_KINDS:
            # List the vocabulary: a typo'd kind in a TOML plan must fail
            # loudly at load time with the fix in the message, not produce
            # a plan whose fault silently never fires.
            raise ValueError(
                f"Unknown fault kind: {self.kind!r}. "
                f"Valid kinds: {', '.join(ALL_KINDS)}"
            )

    def to_dict(self) -> dict[str, Any]:
        return asdict(self)

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "FaultEvent":
        allowed = {f.name for f in fields(cls)}
        unknown = set(data) - allowed
        if unknown:
            raise ValueError(f"Unknown fault event field(s): {sorted(unknown)}")
        return cls(**data)


@dataclass(frozen=True)
class ChaosTimings:
    """Cluster timeout profile a chaos run executes under.

    Production defaults (heartbeats every 10 s, 60 s pong budget) would
    stretch every fault scenario to minutes; the chaos runner temporarily
    compresses them to these values — via the same ``TRC_*`` overrides and
    module constants a real deployment would tune — and restores the
    originals afterwards. The *plan generator* also reads them: an
    eviction-driving ``delay_send`` must out-stall the heartbeat budget,
    and a survivable ``partition`` must fit inside it.
    """

    heartbeat_interval: float = 0.15
    heartbeat_response_timeout: float = 1.2
    heartbeat_pong_retries: int = 1
    max_wait_for_reconnect: float = 2.0
    backoff_base: float = 1.5
    backoff_cap_seconds: float = 0.25
    max_connect_retries: int = 80
    max_reconnects_per_op: int = 80
    op_deadline_seconds: float = 12.0
    send_deadline_seconds: float = 5.0
    rpc_deadline_seconds: float = 4.0

    def eviction_latency_seconds(self) -> float:
        """Worst-case heartbeat path from silence to eviction."""
        return (
            (self.heartbeat_pong_retries + 1) * self.heartbeat_response_timeout
            + self.heartbeat_interval
        )

    def to_dict(self) -> dict[str, Any]:
        return asdict(self)

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "ChaosTimings":
        allowed = {f.name for f in fields(cls)}
        unknown = set(data) - allowed
        if unknown:
            raise ValueError(f"Unknown timing field(s): {sorted(unknown)}")
        return cls(**data)


@dataclass(frozen=True)
class FaultPlan:
    """A complete, reproducible chaos schedule for one cluster run."""

    seed: int
    workers: int
    events: tuple[FaultEvent, ...] = ()
    timings: ChaosTimings = field(default_factory=ChaosTimings)

    # -- queries -------------------------------------------------------------

    def events_for(self, slot: int) -> tuple[FaultEvent, ...]:
        return tuple(e for e in self.events if e.target == slot)

    def master_events(self) -> tuple[FaultEvent, ...]:
        """Control-plane faults (master kill / partition), schedule order."""
        return tuple(
            sorted(
                (e for e in self.events if e.kind in MASTER_KINDS),
                key=lambda e: e.at_seconds,
            )
        )

    def replication_events(self) -> tuple[FaultEvent, ...]:
        """Replication-plane faults (stream partition, router kill,
        follower lag), schedule order."""
        return tuple(
            sorted(
                (e for e in self.events if e.kind in REPLICATION_KINDS),
                key=lambda e: e.at_seconds,
            )
        )

    def expected_evictions(self) -> int:
        return sum(1 for e in self.events if e.causes_eviction)

    def expected_drains(self) -> int:
        return sum(1 for e in self.events if e.kind == KIND_DRAIN)

    def kinds(self) -> set[str]:
        return {e.kind for e in self.events}

    def fingerprint(self) -> str:
        """Stable digest of the schedule — equal iff the schedules are."""
        canonical = json.dumps(self.to_dict(), sort_keys=True)
        return hashlib.sha256(canonical.encode("utf-8")).hexdigest()[:16]

    def to_dict(self) -> dict[str, Any]:
        return {
            "seed": self.seed,
            "workers": self.workers,
            "events": [e.to_dict() for e in self.events],
            "timings": self.timings.to_dict(),
        }

    # -- construction --------------------------------------------------------

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "FaultPlan":
        return cls(
            seed=int(data["seed"]),
            workers=int(data["workers"]),
            events=tuple(FaultEvent.from_dict(e) for e in data.get("events", [])),
            timings=ChaosTimings.from_dict(data.get("timings", {})),
        )

    @classmethod
    def generate(
        cls,
        seed: int,
        workers: int = 3,
        *,
        timings: ChaosTimings | None = None,
        kills: int = 1,
        partitions: int = 1,
        duplicate_sends: int = 1,
        stragglers: int = 1,
        wedges: int = 1,
        drops: int = 1,
        dispatch_delays: int = 1,
        hangs: int = 0,
        drains: int = 0,
        master_kills: int = 0,
        master_partitions: int = 0,
        replication_partitions: int = 0,
        router_kills: int = 0,
        follower_lags: int = 0,
    ) -> "FaultPlan":
        """Roll a schedule from one PCG64 stream.

        Role placement keeps the run completable: every fault that removes
        a worker (kill / hang / wedge-eviction / drain) lands on a distinct
        slot, at least one slot stays alive to the end, and survivable
        faults (partition, straggler, duplicate, drop, dispatch delay) are
        placed on surviving slots so their effects stay observable.
        """
        timings = timings if timings is not None else ChaosTimings()
        lethal = kills + hangs + wedges + drains
        if lethal >= workers:
            raise ValueError(
                f"{lethal} worker-removing fault(s) need at least "
                f"{lethal + 1} workers; got {workers}."
            )
        rng = np.random.Generator(np.random.PCG64(seed))
        order = [int(s) for s in rng.permutation(workers)]
        doomed, survivors = order[:lethal], order[lethal:]

        def survivor(i: int) -> int:
            return survivors[i % len(survivors)]

        events: list[FaultEvent] = []
        cursor = 0
        for _ in range(kills):
            events.append(
                FaultEvent(
                    kind=(
                        KIND_CRASH_BEFORE_RESULT
                        if rng.random() < 0.5
                        else KIND_CRASH_AFTER_RESULT
                    ),
                    target=doomed[cursor],
                    nth=int(rng.integers(2, 5)),
                    causes_eviction=True,
                )
            )
            cursor += 1
        for _ in range(hangs):
            events.append(
                FaultEvent(
                    kind=KIND_HANG,
                    target=doomed[cursor],
                    nth=int(rng.integers(2, 5)),
                    causes_eviction=True,
                )
            )
            cursor += 1
        for _ in range(wedges):
            # A finished-event send stalled well past the heartbeat budget:
            # the pong queue wedges behind it, the master evicts, the frame
            # is re-rendered elsewhere, and the stalled result finally lands
            # late — the duplicate-result race, driven end to end.
            events.append(
                FaultEvent(
                    kind=KIND_DELAY_SEND,
                    target=doomed[cursor],
                    nth=int(rng.integers(2, 4)),
                    duration_seconds=float(
                        timings.eviction_latency_seconds() * rng.uniform(1.8, 2.4)
                    ),
                    match_message_type=FINISHED_EVENT_TYPE,
                    causes_eviction=True,
                )
            )
            cursor += 1
        for _ in range(drains):
            events.append(
                FaultEvent(
                    kind=KIND_DRAIN,
                    target=doomed[cursor],
                    at_seconds=float(rng.uniform(0.8, 1.6)),
                )
            )
            cursor += 1
        for i in range(partitions):
            # Shorter than the pong budget and the master's reconnect wait:
            # the link heals, nobody is evicted, nothing is lost.
            events.append(
                FaultEvent(
                    kind=KIND_PARTITION,
                    target=survivor(i),
                    at_seconds=float(rng.uniform(0.6, 1.4)),
                    duration_seconds=float(
                        min(
                            timings.heartbeat_response_timeout,
                            timings.max_wait_for_reconnect,
                        )
                        * rng.uniform(0.35, 0.6)
                    ),
                )
            )
        for i in range(stragglers):
            events.append(
                FaultEvent(
                    kind=KIND_SLOW_RENDER,
                    target=survivor(partitions + i),
                    multiplier=float(rng.uniform(3.0, 5.0)),
                )
            )
        for i in range(duplicate_sends):
            events.append(
                FaultEvent(
                    kind=KIND_DUPLICATE_SEND,
                    target=survivor(i),
                    nth=int(rng.integers(1, 4)),
                    match_message_type=FINISHED_EVENT_TYPE,
                )
            )
        for i in range(drops):
            # Dropping a started-rendering event is survivable by design:
            # the master merely misses the queued->rendering transition.
            events.append(
                FaultEvent(
                    kind=KIND_DROP_SEND,
                    target=survivor(i + 1),
                    nth=int(rng.integers(1, 3)),
                    match_message_type=RENDERING_EVENT_TYPE,
                )
            )
        for i in range(dispatch_delays):
            events.append(
                FaultEvent(
                    kind=KIND_DELAY_DISPATCH,
                    target=survivor(i),
                    nth=int(rng.integers(1, 3)),
                    duration_seconds=float(rng.uniform(0.2, 0.5)),
                )
            )
        # Control-plane faults draw LAST so plans without them (every
        # pre-HA seed) keep a bit-identical schedule for the same seed.
        for _ in range(master_kills):
            events.append(
                FaultEvent(
                    kind=KIND_MASTER_KILL,
                    target=MASTER_TARGET,
                    at_seconds=float(rng.uniform(0.8, 1.4)),
                )
            )
        for _ in range(master_partitions):
            events.append(
                FaultEvent(
                    kind=KIND_MASTER_PARTITION,
                    target=MASTER_TARGET,
                    at_seconds=float(rng.uniform(0.4, 0.8)),
                )
            )
        # Replication-plane faults draw after the master faults, for the
        # same bit-identity reason: every pre-replication seed (including
        # failover plans with master faults) keeps its exact schedule.
        for _ in range(replication_partitions):
            # Severed before the master kill window (0.8+): the follower
            # must reconnect, gap-detect, and catch back up in time for
            # promotion to still find a current replica.
            events.append(
                FaultEvent(
                    kind=KIND_REPLICATION_PARTITION,
                    target=MASTER_TARGET,
                    at_seconds=float(rng.uniform(0.2, 0.6)),
                    duration_seconds=float(rng.uniform(0.1, 0.3)),
                )
            )
        for _ in range(router_kills):
            events.append(
                FaultEvent(
                    kind=KIND_ROUTER_KILL,
                    target=MASTER_TARGET,
                    at_seconds=float(rng.uniform(0.3, 0.7)),
                    duration_seconds=float(rng.uniform(0.2, 0.5)),
                )
            )
        for _ in range(follower_lags):
            events.append(
                FaultEvent(
                    kind=KIND_FOLLOWER_LAG,
                    target=MASTER_TARGET,
                    at_seconds=float(rng.uniform(0.1, 0.4)),
                    duration_seconds=float(rng.uniform(0.3, 0.8)),
                    multiplier=float(rng.uniform(0.005, 0.02)),
                )
            )
        return cls(
            seed=seed, workers=workers, events=tuple(events), timings=timings
        )

    @classmethod
    def generate_failover(cls, seed: int, workers: int = 3) -> "FaultPlan":
        """A failover-focused schedule: one master kill mid-job plus the
        survivable worker faults (straggler, duplicated result send,
        dropped rendering event) that keep the dedup seam honest while
        the standby adopts the pool. No worker-removing faults — every
        worker must survive to be re-adopted."""
        return cls.generate(
            seed,
            workers,
            kills=0,
            partitions=0,
            wedges=0,
            hangs=0,
            drains=0,
            duplicate_sends=1,
            stragglers=1,
            drops=1,
            dispatch_delays=0,
            master_kills=1,
            master_partitions=1,
        )

    @classmethod
    def generate_replicated_failover(cls, seed: int, workers: int = 3) -> "FaultPlan":
        """A cross-host failover schedule: the replication stream is
        severed and re-established mid-job, the follower is briefly
        lagged, and THEN the primary is killed — promotion must find a
        replica that caught back up over TCP, with no shared filesystem
        to fall back on. Worker faults stay survivable (straggler +
        duplicated result send) so the exactly-once seam is exercised
        across the promotion boundary."""
        return cls.generate(
            seed,
            workers,
            kills=0,
            partitions=0,
            wedges=0,
            hangs=0,
            drains=0,
            duplicate_sends=1,
            stragglers=1,
            drops=1,
            dispatch_delays=0,
            master_kills=1,
            master_partitions=0,
            replication_partitions=1,
            follower_lags=1,
        )

    @classmethod
    def generate_shard_kill(cls, seed: int, workers: int = 4) -> "FaultPlan":
        """A shard-death schedule for the two-shard router scenario: one
        shard's master is killed mid-run (its workers must re-home to the
        survivor through the router) and the router itself is bounced
        once (re-homing must ride out the window). Worker faults stay
        survivable so every worker lives to re-home and the survivor's
        dedup seam still sees a duplicated send."""
        return cls.generate(
            seed,
            workers,
            kills=0,
            partitions=0,
            wedges=0,
            hangs=0,
            drains=0,
            duplicate_sends=1,
            stragglers=1,
            drops=1,
            dispatch_delays=0,
            master_kills=1,
            master_partitions=0,
            router_kills=1,
        )

    @classmethod
    def from_toml(cls, path: str | Path) -> "FaultPlan":
        """Load a plan from TOML: explicit ``[[events]]``, or a seeded
        ``[generate]`` table (kills / partitions / ... counts)."""
        with open(path, "rb") as f:
            data = tomllib.load(f)
        seed = int(data.get("seed", 0))
        workers = int(data.get("workers", 3))
        timings = ChaosTimings.from_dict(data.get("timings", {}))
        if "events" in data:
            return cls(
                seed=seed,
                workers=workers,
                events=tuple(FaultEvent.from_dict(e) for e in data["events"]),
                timings=timings,
            )
        counts = {k: int(v) for k, v in data.get("generate", {}).items()}
        return cls.generate(seed, workers, timings=timings, **counts)

    @classmethod
    def from_env(cls) -> "FaultPlan":
        """``TRC_CHAOS_PLAN`` (TOML path) wins; else a generated plan from
        ``TRC_CHAOS_SEED`` / ``TRC_CHAOS_WORKERS`` (defaults 0 / 3)."""
        plan_path = env_str("TRC_CHAOS_PLAN")
        if plan_path:
            return cls.from_toml(plan_path)
        return cls.generate(
            env_int("TRC_CHAOS_SEED", 0),
            env_int("TRC_CHAOS_WORKERS", 3),
        )
