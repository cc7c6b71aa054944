"""From a profiler trace to device numbers: busy union, kernel sums, idle
gaps labelled by what the host was doing.

Two halves. `read_trace_directory` needs JAX to parse the profiler's
`.xplane.pb`, so the harness runs this file as a CPU-pinned child
(`python trace_reduce.py <trace dir> <out.json>`) once the workers have
gone. Everything else is arithmetic on lists of `[name, start_s, end_s]`
and is what the tests check on a synthetic trace.

Times inside a trace count from the trace's start. The worker's entry
leaves one host event, `bench_clock_mark`, taken at a known wall-clock
time; `to_wall` moves device intervals onto the clock that the worker's
own phase spans use.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from pathlib import Path

CLOCK_MARK = "bench_clock_mark"
OPS_LINE = "XLA Ops"
# Lines of a device plane that hold whole steps or modules, not operations.
ENVELOPE_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope", "Source code")

Interval = tuple[str, float, float]


def read_trace_directory(directory: Path) -> dict:
    """Device operations per device plane and the clock mark, in seconds
    from the trace's start. Needs JAX: run in a child."""
    import jax

    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        return {"devices": [], "mark_s": None, "planes": []}
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices, planes, mark = [], [], None
    for plane in data.planes:
        lines = []
        is_device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper()
        for line in plane.lines:
            events, count = [], 0
            for event in line.events:
                count += 1
                if event.name == CLOCK_MARK and mark is None:
                    mark = event.start_ns / 1e9
                if is_device:
                    events.append([
                        event.name, event.start_ns / 1e9,
                        (event.start_ns + event.duration_ns) / 1e9,
                    ])
            lines.append({"name": line.name, "events": events, "count": count})
        planes.append({"name": plane.name, "lines": [
            {"name": l["name"], "events": l["count"]} for l in lines
        ]})
        if is_device:
            devices.append({"name": plane.name, "lines": lines})
    return {"devices": devices, "mark_s": mark, "planes": planes}


def device_operations(device: dict) -> list[Interval]:
    """The operations that ran on one device: its `XLA Ops` line, or, where
    the profiler names its lines otherwise, every line that is not an
    envelope of whole steps or modules."""
    lines = [l for l in device["lines"] if l["name"] == OPS_LINE] or [
        l for l in device["lines"] if l["name"] not in ENVELOPE_LINES
    ]
    return sorted(
        ((name, start, end) for l in lines for name, start, end in l["events"] if end > start),
        key=lambda op: op[1],
    )


def to_wall(operations: list[Interval], mark_s: float, mark_wall_s: float) -> list[Interval]:
    shift = mark_wall_s - mark_s
    return [(name, start + shift, end + shift) for name, start, end in operations]


def clip(operations: list[Interval], start: float, end: float) -> list[Interval]:
    return [
        (name, max(a, start), min(b, end)) for name, a, b in operations
        if b > start and a < end
    ]


def busy_union(operations: list[Interval]) -> list[tuple[float, float]]:
    """Merged intervals in which at least one operation ran (operations
    nest and overlap: a while loop spans its body's operations)."""
    merged: list[list[float]] = []
    for _, start, end in sorted(operations, key=lambda op: op[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(operations: list[Interval]) -> float:
    return sum(b - a for a, b in busy_union(operations))


def idle_gaps(operations: list[Interval], start: float, end: float) -> list[tuple[float, float]]:
    """The parts of [start, end] in which nothing ran on the device."""
    gaps, cursor = [], start
    for a, b in busy_union(clip(operations, start, end)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if end > cursor:
        gaps.append((cursor, end))
    return gaps


def covered_seconds(spans: list[Interval], intervals: list[tuple[float, float]]) -> float:
    """Seconds of `intervals` (sorted, disjoint) that lie inside `spans`
    (sorted, disjoint)."""
    total, at = 0.0, 0
    for _, start, end in spans:
        while at < len(intervals) and intervals[at][1] <= start:
            at += 1
        scan = at
        while scan < len(intervals) and intervals[scan][0] < end:
            total += min(end, intervals[scan][1]) - max(start, intervals[scan][0])
            scan += 1
    return total


def kernel_seconds(operations: list[Interval], pattern: str) -> float:
    """Busy seconds of the operations whose name matches `pattern` (the
    union, so a kernel counted under two nested names counts once)."""
    matcher = re.compile(pattern, re.IGNORECASE)
    return busy_seconds([op for op in operations if matcher.search(op[0])])


# Opcodes whose time is their bodies': a loop spans the operations inside it.
ENVELOPE_OPCODES = ("while", "conditional", "call")


def short_name(name: str) -> tuple[str, str | None]:
    """(short name, opcode). The profiler names a device operation by its
    whole HLO text, `%fusion.3 = s32[2097152]{...} fusion(...), kind=...`:
    keep the instruction's name, its opcode and its result's shape."""
    match = re.match(r"(%?[\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?.*?\s([a-z][a-z\-]*)\(", name)
    if match is None:
        return name[:120], None
    instruction, shape, opcode = match.groups()
    return " ".join(part for part in (instruction, opcode, shape) if part), opcode


def top_operations(operations: list[Interval], count: int = 10) -> list[list]:
    """The device operations that took most time, by name, in seconds.
    Envelopes (loops) are left out; copies that overlap a kernel count in
    full, so the list can add up to more than the busy time."""
    totals: dict[str, float] = {}
    for name, start, end in operations:
        name, opcode = short_name(name)
        if opcode not in ENVELOPE_OPCODES:
            totals[name] = totals.get(name, 0.0) + (end - start)
    ranked = sorted(totals.items(), key=lambda item: -item[1])[:count]
    return [[name, seconds] for name, seconds in ranked]


def label_gaps(
    gaps: list[tuple[float, float]], spans: list[Interval],
    operations: list[Interval], count: int = 10,
) -> list[list]:
    """Idle seconds by what the host was doing, largest first.

    The label comes from the worker's own phase spans (`read`, `render`,
    `write`, on the wall clock, one at a time): the phase that covers the
    gap's middle, or `between_frames` where none does. Inside `render`,
    whether a device operation of that render ended before the gap (else
    the host was still dispatching) and whether one started after it (else
    the host was reading the result back)."""
    spans = sorted(spans, key=lambda span: span[1])
    span_starts = [span[1] for span in spans]
    op_starts = sorted(op[1] for op in operations)
    op_ends = sorted(op[2] for op in operations)
    edges = sorted({edge for span in spans for edge in span[1:]})
    totals: dict[str, float] = {}
    pieces = []
    for gap_start, gap_end in gaps:  # a gap is cut where a phase begins or ends
        inside = edges[bisect.bisect_right(edges, gap_start):bisect.bisect_left(edges, gap_end)]
        cuts = [gap_start, *inside, gap_end]
        pieces.extend(zip(cuts, cuts[1:]))
    for gap_start, gap_end in pieces:
        label = "between_frames"
        at = bisect.bisect_right(span_starts, (gap_start + gap_end) / 2.0) - 1
        if at >= 0 and (gap_start + gap_end) / 2.0 < spans[at][2]:
            label, span_start, span_end = spans[at]
            if label == "render":
                last_end = bisect.bisect_right(op_ends, gap_start) - 1
                next_start = bisect.bisect_left(op_starts, gap_end)
                if last_end < 0 or op_ends[last_end] < span_start:
                    label = "render:dispatch"
                elif next_start >= len(op_starts) or op_starts[next_start] > span_end:
                    label = "render:readback"
                else:
                    label = "render:host_between_ops"
        totals[label] = totals.get(label, 0.0) + (gap_end - gap_start)
    ranked = sorted(totals.items(), key=lambda item: -item[1])[:count]
    return [[name, seconds] for name, seconds in ranked]


def worker_phase_spans(trace_events_path: Path) -> list[Interval]:
    """`read`/`render`/`write` spans of a worker's exported timeline, in
    wall-clock seconds (the exporter writes microseconds)."""
    document = json.loads(Path(trace_events_path).read_text())
    spans = [
        (e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
        for e in document.get("traceEvents", [])
        if e.get("ph") == "X" and e.get("cat") == "worker"
        and e.get("name") in ("read", "render", "write")
    ]
    return sorted(spans, key=lambda span: span[1])


if __name__ == "__main__":
    Path(sys.argv[2]).write_text(json.dumps(read_trace_directory(Path(sys.argv[1]))))
