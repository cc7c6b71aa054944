"""Trace-invariant checker for exported Chrome trace-event artifacts.

Every timeline this repo writes — per-run ``*_trace-events.json``, the
merged ``*_cluster_trace-events.json``, worker-daemon exports — must hold
a small set of structural invariants or the Perfetto view silently lies
(mis-nested slices, arrows pointing nowhere, two processes folded onto one
row). ``validate_trace_events`` returns a list of human-readable problem
strings (empty = valid):

1.  Every event is an object with a ``ph``; complete (``X``) events carry
    finite, non-negative ``ts`` and ``dur``; all timestamped events carry
    finite non-negative ``ts``.
2.  ``B``/``E`` duration events balance per (pid, tid) in stack order.
3.  Per (pid, tid) track, ``X`` events appear in non-decreasing END-time
    order (the tracer appends at completion, so out-of-order ends mean a
    clock went backwards or a merge interleaved two tracks onto one tid).
    A small tolerance absorbs wall-vs-monotonic rounding.
4.  Metadata is unique: one ``process_name`` per pid, one ``thread_name``
    per (pid, tid) — conflicting claims are exactly the pid-collision bug
    a bad multi-process merge produces.
5.  Flow ids resolve: no half-open arrows — an id with a start (``s``)
    must carry a terminal (``f``) and vice versa — and every flow event
    binds inside some ``X`` span on its own (pid, tid) track. Step-only
    (``t``) chains are legal: a per-process fragment (a worker daemon's
    own export) routes flows whose start and terminal live on the
    master's timeline; the merged cluster file carries all three.
6.  Attribution tracks are self-contained: the ``sched`` (tick profiler)
    and ``loop`` (loop-lag monitor) rows carry only complete (``X``) and
    instant (``i``) events — a ``B``/``E`` or flow event landing there
    means a merge folded another track onto an attribution row.

7.  A process's start-up is stitched right: its ``worker.startup`` spans
    (obs/startup.py) name stages of ``STARTUP_STAGES``, each at most once
    and in that order, do not overlap, and leave no gap over 1 ms — the
    stages are exclusive and contiguous by construction, so anything else
    means a mark was set twice or a merge mixed two processes' start-ups.
    A worker that died before its first frame exports a prefix of them.

8.  A worker's steps keep their two clocks and their parts apart: a
    ``worker.step`` event's ``args.cpu_s`` (its thread's CPU seconds,
    read inside the wall clock's reads), where present, is at most its
    ``dur`` plus 1 ms — or plus one tick of the CPU clock where the
    process's own readings show a coarser one (the chip's host counts a
    thread's CPU in hundredths of a second: every reading is a multiple
    of 0.01, and a step that a tick's edge falls in reads a whole tick)
    — and a ``file_write`` event's file operations (``args.<op>_ms``,
    obs/tracer.py's step of that name parted by ``FILE_WRITE_OPS``) add
    up to at most its ``dur`` (plus the rounding of the five).

``scripts/validate_trace.py`` is the CLI wrapper; tests call these
functions directly on every artifact they export.
"""

from __future__ import annotations

import bisect
import json
import math
from pathlib import Path
from typing import Any, Iterable

from tpu_render_cluster.obs.startup import STARTUP_STAGES
from tpu_render_cluster.obs.tracer import FILE_WRITE_OPS

__all__ = [
    "validate_trace_events",
    "validate_trace_document",
    "validate_trace_file",
    "validate_blackbox_document",
    "validate_blackbox_file",
]

# End-time ordering tolerance per track, in trace microseconds. Spans anchor
# on wall-clock but measure duration on the monotonic clock, so two spans
# completing back-to-back can disagree about "now" by the rounding jitter
# between the clocks; 5 ms is far above that and far below any real
# ordering violation a merge or rebase bug would introduce.
END_ORDER_TOLERANCE_US = 5000.0

# Start-up stages share their edges: a gap is a stitching fault, an overlap
# beyond the timestamps' rounding too.
STARTUP_GAP_TOLERANCE_US = 1000.0
STARTUP_OVERLAP_TOLERANCE_US = 1.0

# A step's CPU seconds lie inside its wall seconds by the order the clocks
# are read in; the two clocks tick apart by less than this, or by one tick
# of a CPU clock that is coarser (``_cpu_clock_tick_us``).
STEP_CPU_TOLERANCE_US = 1000.0
CPU_TICK_MIN_READINGS = 8
# The five operations' milliseconds are each rounded to four places.
FILE_OPS_ROUNDING_US = 1.0
FILE_WRITE_OP_KEYS = tuple(f"{op}_ms" for op in FILE_WRITE_OPS)


def _finite_nonneg(value: Any) -> bool:
    return (
        isinstance(value, (int, float))
        and not isinstance(value, bool)
        and math.isfinite(value)
        and value >= 0
    )


def _cpu_clock_tick_us(readings: list[Any]) -> float:
    """The tick of a CPU clock that counts in ticks, as its readings show
    it: the smallest of them that is not 0, where there are enough of them
    and every one is a multiple of it; 0 for a clock as fine as Linux's,
    whose readings share no such measure."""
    counted = [r * 1e6 for r in readings if _finite_nonneg(r) and r > 0]
    if len(counted) < CPU_TICK_MIN_READINGS:
        return 0.0
    tick = min(counted)
    in_ticks = all(abs(r / tick - round(r / tick)) * tick <= 1.0 for r in counted)
    return tick if in_ticks else 0.0


def _step_problems(steps: list[tuple[int, dict[str, Any]]]) -> list[str]:
    """Invariant 8 on one process's ``worker.step`` events."""
    problems: list[str] = []
    readings = [
        (event.get("args") or {}).get("cpu_s") for _, event in steps
    ]
    tolerance = max(STEP_CPU_TOLERANCE_US, _cpu_clock_tick_us(readings))
    for (i, event), cpu_s in zip(steps, readings):
        dur = float(event["dur"])
        if cpu_s is not None and (
            not _finite_nonneg(cpu_s) or cpu_s * 1e6 > dur + tolerance
        ):
            problems.append(
                f"event #{i} ({event.get('name')!r}): cpu_s {cpu_s!r} is more "
                f"than the step's {dur:.1f}us of wall time (and a tick of "
                f"{tolerance:.0f}us)"
            )
        problems.extend(_file_operation_problems(i, event))
    return problems


def _file_operation_problems(i: int, event: dict[str, Any]) -> list[str]:
    problems: list[str] = []
    args = event.get("args") or {}
    dur = float(event["dur"])
    operations = [args[key] for key in FILE_WRITE_OP_KEYS if key in args]
    if operations and event.get("name") == "file_write":
        if not all(_finite_nonneg(ms) for ms in operations):
            problems.append(f"event #{i} ('file_write'): a file operation's ms is no number")
        elif sum(operations) * 1e3 > dur + FILE_OPS_ROUNDING_US:
            problems.append(
                f"event #{i} ('file_write'): its file operations add up to "
                f"{sum(operations) * 1e3:.1f}us, more than the step's {dur:.1f}us"
            )
    return problems


def validate_trace_events(events: Iterable[Any]) -> list[str]:
    problems: list[str] = []
    spans_by_track: dict[tuple[Any, Any], list[dict[str, Any]]] = {}
    open_stacks: dict[tuple[Any, Any], list[str]] = {}
    process_names: dict[Any, str] = {}
    thread_names: dict[tuple[Any, Any], str] = {}
    flow_events: list[dict[str, Any]] = []
    phases_by_track: dict[tuple[Any, Any], set[str]] = {}
    startup_by_pid: dict[Any, list[dict[str, Any]]] = {}
    steps_by_pid: dict[Any, list[tuple[int, dict[str, Any]]]] = {}

    for i, event in enumerate(events):
        if not isinstance(event, dict) or "ph" not in event:
            problems.append(f"event #{i}: not an object with a 'ph' field")
            continue
        ph = event["ph"]
        track = (event.get("pid"), event.get("tid"))
        if ph == "M":
            name = event.get("name")
            claimed = (event.get("args") or {}).get("name")
            if name == "process_name":
                previous = process_names.setdefault(event.get("pid"), claimed)
                if previous != claimed:
                    problems.append(
                        f"pid {event.get('pid')}: conflicting process_name "
                        f"metadata ({previous!r} vs {claimed!r})"
                    )
            elif name == "thread_name":
                previous = thread_names.setdefault(track, claimed)
                if previous != claimed:
                    problems.append(
                        f"track {track}: conflicting thread_name metadata "
                        f"({previous!r} vs {claimed!r})"
                    )
            continue
        phases_by_track.setdefault(track, set()).add(str(ph))
        if not _finite_nonneg(event.get("ts")):
            problems.append(
                f"event #{i} ({event.get('name')!r}, ph={ph!r}): "
                f"missing or negative ts"
            )
            continue
        if ph == "X":
            if not _finite_nonneg(event.get("dur")):
                problems.append(
                    f"event #{i} ({event.get('name')!r}): complete event "
                    f"with missing or negative dur"
                )
                continue
            spans_by_track.setdefault(track, []).append(event)
            if event.get("cat") == "worker.startup":
                startup_by_pid.setdefault(event.get("pid"), []).append(event)
            elif event.get("cat") == "worker.step":
                steps_by_pid.setdefault(event.get("pid"), []).append((i, event))
        elif ph == "B":
            open_stacks.setdefault(track, []).append(str(event.get("name")))
        elif ph == "E":
            stack = open_stacks.setdefault(track, [])
            if not stack:
                problems.append(
                    f"track {track}: 'E' event ({event.get('name')!r}) "
                    f"with no open 'B'"
                )
            else:
                stack.pop()
        elif ph in ("s", "t", "f"):
            flow_events.append(event)

    for track, stack in open_stacks.items():
        if stack:
            problems.append(
                f"track {track}: {len(stack)} unclosed 'B' event(s): {stack}"
            )

    # Invariant 6: attribution tracks carry only self-contained events.
    for track, name in thread_names.items():
        if name not in ("sched", "loop"):
            continue
        stray = phases_by_track.get(track, set()) - {"X", "i"}
        if stray:
            problems.append(
                f"track {track} ({name!r}): event phase(s) {sorted(stray)} "
                f"on an attribution track (only 'X' and 'i' belong there)"
            )

    # Per-track monotonic end times (completion order is append order).
    for track, spans in spans_by_track.items():
        high_water = -math.inf
        for span in spans:
            end = float(span["ts"]) + float(span["dur"])
            if end < high_water - END_ORDER_TOLERANCE_US:
                problems.append(
                    f"track {track}: span {span.get('name')!r} ends at "
                    f"{end:.1f}us, {high_water - end:.1f}us before an "
                    f"earlier-appended span's end (non-monotonic track)"
                )
            high_water = max(high_water, end)

    # Invariant 8: each process's steps, CPU inside wall and parts inside whole.
    for steps in steps_by_pid.values():
        problems.extend(_step_problems(steps))

    # Invariant 7: each process's start-up stages, in order and edge to edge.
    for pid, stages in startup_by_pid.items():
        previous = None
        for span in stages:
            name = span.get("name")
            if name not in STARTUP_STAGES:
                problems.append(f"pid {pid}: unknown start-up stage {name!r}")
                continue
            if previous is not None:
                if STARTUP_STAGES.index(name) <= STARTUP_STAGES.index(previous["name"]):
                    problems.append(
                        f"pid {pid}: start-up stage {name!r} after "
                        f"{previous['name']!r} (out of STARTUP_STAGES order)"
                    )
                gap = float(span["ts"]) - (float(previous["ts"]) + float(previous["dur"]))
                if gap > STARTUP_GAP_TOLERANCE_US:
                    problems.append(
                        f"pid {pid}: {gap:.1f}us between start-up stages "
                        f"{previous['name']!r} and {name!r} (they share an edge)"
                    )
                elif gap < -STARTUP_OVERLAP_TOLERANCE_US:
                    problems.append(
                        f"pid {pid}: start-up stages {previous['name']!r} and "
                        f"{name!r} overlap by {-gap:.1f}us"
                    )
            previous = span

    # Flow resolution: start + terminal per id, every event bound to a span.
    # Binding is a point-stabbing query per flow event; a linear scan over
    # the track's spans is quadratic on production artifacts (a 14400-frame
    # job puts ~60k spans and as many flow steps on one track). Sorting by
    # start with a running max-end answers "does any span contain ts?" in
    # O(log n): a containing span exists iff the max end among spans
    # starting at or before ts reaches ts.
    stab_index: dict[tuple[Any, Any], tuple[list[float], list[float]]] = {}
    for track, spans in spans_by_track.items():
        intervals = sorted(
            (float(s["ts"]), float(s["ts"]) + float(s["dur"])) for s in spans
        )
        starts = [start for start, _ in intervals]
        max_ends: list[float] = []
        high = -math.inf
        for _, end in intervals:
            high = max(high, end)
            max_ends.append(high)
        stab_index[track] = (starts, max_ends)

    phases_by_id: dict[Any, set[str]] = {}
    for event in flow_events:
        phases_by_id.setdefault(event.get("id"), set()).add(event["ph"])
        track = (event.get("pid"), event.get("tid"))
        ts = float(event["ts"])
        starts, max_ends = stab_index.get(track, ([], []))
        index = bisect.bisect_right(starts, ts) - 1
        bound = index >= 0 and max_ends[index] >= ts
        if not bound:
            problems.append(
                f"flow {event.get('id')!r} ({event['ph']}) at {ts:.1f}us on "
                f"track {track}: no enclosing span to bind to"
            )
    for flow_id, phases in phases_by_id.items():
        # Step-only chains are per-process fragments (start/terminal live
        # on another process's timeline); half-open chains are broken.
        if "s" in phases and "f" not in phases:
            problems.append(
                f"flow {flow_id!r}: start ('s') without terminal ('f')"
            )
        elif "f" in phases and "s" not in phases:
            problems.append(
                f"flow {flow_id!r}: terminal ('f') without start ('s')"
            )

    return problems


def validate_trace_document(document: Any) -> list[str]:
    """Validate a parsed trace document (object or bare-array format)."""
    if isinstance(document, dict):
        events = document.get("traceEvents")
        if not isinstance(events, list):
            return ["document: 'traceEvents' missing or not a list"]
    elif isinstance(document, list):
        events = document
    else:
        return ["document: not a Chrome trace-event document"]
    return validate_trace_events(events)


def validate_trace_file(path: str | Path) -> list[str]:
    path = Path(path)
    try:
        document = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    return [f"{path}: {p}" for p in validate_trace_document(document)]


def validate_blackbox_document(document: Any) -> list[str]:
    """Validate a flight-recorder blackbox bundle (obs/flightrec.py).

    A bundle IS a trace document (its ``traceEvents`` must satisfy every
    trace invariant — a post-mortem that lies in Perfetto is worse than
    none) plus a ``blackbox`` section whose window must be coherent: a
    finite ``[t0, t1]`` ordered pair with ``dumped_at`` at the closing
    edge, and every metric sample / protocol digest stamped inside it.
    """
    problems = validate_trace_document(document)
    if not isinstance(document, dict):
        return problems
    box = document.get("blackbox")
    if not isinstance(box, dict):
        problems.append("blackbox: section missing or not an object")
        return problems
    trigger = box.get("trigger")
    if not isinstance(trigger, str) or not trigger:
        problems.append("blackbox: missing trigger")
    window = box.get("window")
    if (
        not isinstance(window, list)
        or len(window) != 2
        or not all(
            isinstance(edge, (int, float)) and math.isfinite(edge)
            for edge in window
        )
        or window[0] > window[1]
    ):
        problems.append(f"blackbox: malformed window {window!r}")
        return problems
    t0, t1 = float(window[0]), float(window[1])
    dumped_at = box.get("dumped_at")
    if not isinstance(dumped_at, (int, float)) or not (
        t0 <= float(dumped_at) <= t1 + 1e-6
    ):
        problems.append(
            f"blackbox: dumped_at {dumped_at!r} outside window [{t0}, {t1}]"
        )
    # A fraction of a sampling interval of slack at the edges: the sampler
    # stamps before the recorder computes its cut.
    slack = 1e-3
    previous_t = -math.inf
    for i, sample in enumerate(box.get("metric_samples") or []):
        at = sample.get("t") if isinstance(sample, dict) else None
        if not isinstance(at, (int, float)) or not (
            t0 - slack <= float(at) <= t1 + slack
        ):
            problems.append(
                f"blackbox: metric sample #{i} at {at!r} outside the window"
            )
            continue
        if float(at) < previous_t:
            problems.append(
                f"blackbox: metric sample #{i} out of time order"
            )
        previous_t = float(at)
    for i, event in enumerate(box.get("protocol_events") or []):
        at = event.get("t") if isinstance(event, dict) else None
        if not isinstance(at, (int, float)) or not (
            t0 - slack <= float(at) <= t1 + slack
        ):
            problems.append(
                f"blackbox: protocol event #{i} at {at!r} outside the window"
            )
    return problems


def validate_blackbox_file(path: str | Path) -> list[str]:
    path = Path(path)
    try:
        document = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    return [f"{path}: {p}" for p in validate_blackbox_document(document)]
