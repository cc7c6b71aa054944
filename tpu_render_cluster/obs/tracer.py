"""Span tracer with Chrome trace-event export.

Spans carry BOTH clocks: wall-clock (``time.time``) anchors the span on the
trace timeline (and lets traces from different processes line up), and the
monotonic clock (``time.perf_counter``) measures the duration, immune to
NTP steps. Export is the Chrome trace-event JSON object format —
``{"traceEvents": [...]}`` with ``ph: "X"`` complete events — which loads
directly in Perfetto (https://ui.perfetto.dev) or chrome://tracing.

Each ``Tracer`` is one *process row* in the viewer (``pid``); tracks within
it (``tid``) are named virtual threads, so asyncio tasks that interleave on
one OS thread still render as separate, properly-nested lanes. The in-
process harness merges the master tracer and every worker tracer into one
file via ``export_chrome_trace`` — indistinguishable from a multi-host
collection.
"""

from __future__ import annotations

import itertools
import json
import logging
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Iterable, Iterator, Mapping

__all__ = [
    "CPU_TIMED_STEPS",
    "FILE_WRITE_OPS",
    "FRAME_STEPS",
    "Tracer",
    "export_chrome_trace",
    "frame_steps",
    "step",
]

logger = logging.getLogger(__name__)

_pid_counter = itertools.count(1)

# Bounded event buffers: the cap keeps a runaway instrumentation site from
# eating the process's heap; once full, the NEWEST events are dropped (and
# counted). Sized so that the source's largest job on ONE worker drops
# nothing: 14400 frames x (4 phase spans + 4 flow steps + 6 step segments)
# is 201,600 events.
MAX_EVENTS = 300_000

# The steps of one frame, the same for every unit shape. Exclusive PER
# THREAD (the sink below is thread-local): the first two are the frame's
# issue on the worker queue's issue thread, the next two its collect on
# the collect thread (together the device stage), the last two its save
# stage on a save thread, and on any of them at most one step is open at
# an instant. Across threads they overlap: the worker's queue saves frame
# i and issues frame i+2 while it waits for frame i+1 (worker/queue.py),
# so frame i's ``encode`` and ``file_write`` and frame i+2's ``dispatch``
# lie under frame i+1's ``device_wait``, and several frames' ``encode``
# lie beside each other where a save outlasts a device stage. A frame's
# own steps never overlap each other and are handed over together, in the
# order they ended:
#   resolve      scene name, tile region, unit shape, compiled-renderer fetch
#   dispatch     host time issuing device work that does not block (asking
#                for the copy back included)
#   device_wait  host blocked until the device has a result
#   readback     what is left of the device-to-host copy after the wait
#   encode       pixels to the output format's bytes, in memory
#   file_write   output path, mkdir, temporary file, write, close, rename
FRAME_STEPS = ("resolve", "dispatch", "device_wait", "readback", "encode", "file_write")

# The steps whose own thread's CPU clock (``time.thread_time``) is read at
# a segment's two ends, beside the wall clock: the three a metric reads.
# Not all six: on the chip's host (gVisor) the read is a call into the
# sentry of 6 us, where the wall clock's is 0.09 (PERF.md §5), and a
# frame's other four segments would add eight of them to the issue and
# collect threads for numbers nobody reads.
CPU_TIMED_STEPS = ("device_wait", "encode", "file_write")

# What the ``file_write`` step does to the file system, in the order it
# does it (render/image_io.py::write_image times each, edge to edge):
# ``mkdir``, ``create`` (``mkstemp``) and ``rename`` work on the output
# directory, ``write`` and ``close`` on the file's bytes.
FILE_WRITE_OPS = ("mkdir", "create", "write", "close", "rename")

_steps_local = threading.local()


class _Segment:
    """One uninterrupted stretch of a step on this thread."""

    __slots__ = ("name", "start_wall", "start_mono", "start_cpu", "annotation")

    def __init__(self, name: str) -> None:
        self.name = name
        # The clocks are read first and last, so that a frame's segments
        # leave none of its time between them. This thread's CPU clock,
        # for the steps that have one, is read inside the wall clock's two
        # reads, at both ends, so a segment's CPU seconds never pass its
        # wall seconds by more than a tick of the CPU clock.
        self.start_wall = time.time()
        self.start_mono = time.perf_counter()
        self.start_cpu = time.thread_time() if name in CPU_TIMED_STEPS else None
        # Only a process that already imported JAX gets the annotation (the
        # master never imports it). Outside a profiler session a
        # TraceAnnotation is a check of one atomic.
        jax = sys.modules.get("jax")
        self.annotation = (
            jax.profiler.TraceAnnotation("trc:" + name) if jax is not None else None
        )
        if self.annotation is not None:
            self.annotation.__enter__()

    def close(self) -> None:
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
        sink = getattr(_steps_local, "sink", None)
        if sink is not None:
            # (never below 0: a counter is fed with it, and a host's CPU
            # clock that stepped back once would take the worker with it)
            cpu_seconds = (
                None if self.start_cpu is None
                else max(0.0, time.thread_time() - self.start_cpu)
            )
            sink.append(
                (
                    self.name,
                    self.start_wall,
                    time.perf_counter() - self.start_mono,
                    cpu_seconds,
                )
            )


@contextmanager
def step(name: str) -> Iterator[None]:
    """One of FRAME_STEPS, timed where it happens, EXCLUSIVELY on its
    thread.

    A step opened inside another suspends the outer one until it ends, so
    the steps one thread takes for a frame never overlap and add up to
    that thread's stage of the frame. Each
    uninterrupted stretch (a suspended step resumes as a new one) is
    remembered as ``(name, start_wall, seconds, cpu_seconds)`` for the
    frame in hand (``frame_steps``; nothing is kept outside one), the
    last being what this thread's CPU clock (``time.thread_time``) ran
    between the two reads of the wall clock, and None for a step outside
    ``CPU_TIMED_STEPS``: wall less CPU is time the thread held the step
    and did not run. It lies inside a
    ``jax.profiler.TraceAnnotation("trc:<name>")`` so a profile taken by
    anyone carries the program's steps on the profile's own clock.
    """
    if name not in FRAME_STEPS:
        raise ValueError(f"unknown frame step {name!r} (have: {FRAME_STEPS})")
    stack = getattr(_steps_local, "stack", None)
    if stack is None:
        stack = _steps_local.stack = []
    if stack:
        stack[-1].close()
    stack.append(_Segment(name))
    try:
        yield
    finally:
        stack.pop().close()
        if stack:
            stack[-1] = _Segment(stack[-1].name)


@contextmanager
def frame_steps() -> Iterator[list[tuple[str, float, float, float | None]]]:
    """Collect this thread's steps for one frame; yields the list they
    land in, in the order they ended."""
    previous = getattr(_steps_local, "sink", None)
    sink: list[tuple[str, float, float, float | None]] = []
    _steps_local.sink = sink
    try:
        yield sink
    finally:
        _steps_local.sink = previous


class Tracer:
    """Thread-safe span collector for one logical process."""

    def __init__(
        self, process_name: str, *, pid: int | None = None, max_events: int = MAX_EVENTS
    ) -> None:
        self.process_name = process_name
        self.pid = next(_pid_counter) if pid is None else pid
        self._max_events = max_events
        self._lock = threading.Lock()
        self._events: list[dict[str, Any]] = []
        self._dropped = 0
        self._tracks: dict[str, int] = {}
        # What tells this process row from its siblings beyond its name
        # (a worker: which chip it held); exported as the viewer's
        # ``process_labels`` metadata, keys kept beside the label text.
        self.process_labels: dict[str, Any] = {}

    # -- recording -----------------------------------------------------------

    def _tid(self, track: str | None) -> int:
        if track is None:
            return threading.get_ident() & 0x7FFFFFFF
        with self._lock:
            tid = self._tracks.get(track)
            if tid is None:
                tid = len(self._tracks) + 1
                self._tracks[track] = tid
            return tid

    def _append(self, event: dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append(event)

    def complete(
        self,
        name: str,
        *,
        cat: str = "",
        start_wall: float,
        duration: float,
        track: str | None = None,
        args: Mapping[str, Any] | None = None,
    ) -> None:
        """Record a finished span from explicit timestamps (seconds)."""
        event: dict[str, Any] = {
            "name": name,
            "cat": cat or "default",
            "ph": "X",
            "pid": self.pid,
            "tid": self._tid(track),
            "ts": round(start_wall * 1e6, 3),
            "dur": round(max(0.0, duration) * 1e6, 3),
        }
        if args:
            event["args"] = dict(args)
        self._append(event)

    def instant(
        self,
        name: str,
        *,
        cat: str = "",
        track: str | None = None,
        args: Mapping[str, Any] | None = None,
    ) -> None:
        event: dict[str, Any] = {
            "name": name,
            "cat": cat or "default",
            "ph": "i",
            "s": "t",
            "pid": self.pid,
            "tid": self._tid(track),
            "ts": round(time.time() * 1e6, 3),
        }
        if args:
            event["args"] = dict(args)
        self._append(event)

    # -- flow events ---------------------------------------------------------
    #
    # Perfetto flow events ("s" start / "t" step / "f" end, matched by id)
    # draw arrows between spans on different process rows — the causal link
    # from a master-side assignment to the worker-side frame phases. A flow
    # event binds to the slice that encloses its ``ts`` on its (pid, tid)
    # track, so emitters place the flow timestamp INSIDE the span it should
    # attach to (mid-span is the safe choice for zero-duration spans).

    def _flow(
        self,
        phase: str,
        name: str,
        *,
        id: str,
        ts: float,
        cat: str = "",
        track: str | None = None,
        args: Mapping[str, Any] | None = None,
    ) -> None:
        event: dict[str, Any] = {
            "name": name,
            "cat": cat or "flow",
            "ph": phase,
            "id": id,
            "pid": self.pid,
            "tid": self._tid(track),
            "ts": round(ts * 1e6, 3),
        }
        if phase == "f":
            event["bp"] = "e"  # bind the arrowhead to the enclosing slice
        if args:
            event["args"] = dict(args)
        self._append(event)

    def flow_start(self, name: str, *, id: str, ts: float, **kwargs: Any) -> None:
        """Open a flow arrow (source side) at wall time ``ts`` (seconds)."""
        self._flow("s", name, id=id, ts=ts, **kwargs)

    def flow_step(self, name: str, *, id: str, ts: float, **kwargs: Any) -> None:
        """Route an open flow through the span enclosing ``ts``."""
        self._flow("t", name, id=id, ts=ts, **kwargs)

    def flow_end(self, name: str, *, id: str, ts: float, **kwargs: Any) -> None:
        """Terminate a flow arrow (sink side) at wall time ``ts``."""
        self._flow("f", name, id=id, ts=ts, **kwargs)

    @contextmanager
    def span(
        self,
        name: str,
        *,
        cat: str = "",
        track: str | None = None,
        args: Mapping[str, Any] | None = None,
    ):
        """Context manager span: wall-clock anchor, monotonic duration."""
        start_wall = time.time()
        start_mono = time.perf_counter()
        try:
            yield
        finally:
            self.complete(
                name,
                cat=cat,
                start_wall=start_wall,
                duration=time.perf_counter() - start_mono,
                track=track,
                args=args,
            )

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        """Drop buffered events (and the dropped counter); track-name
        assignments persist so tids stay stable across exports. Exporters
        of long-lived shared tracers (the process-global one) call this
        after a write so the next artifact holds only its own run's
        spans."""
        with self._lock:
            self._events.clear()
            self._dropped = 0

    @property
    def dropped(self) -> int:
        return self._dropped

    def metadata_events(self) -> list[dict[str, Any]]:
        """process_name / thread_name metadata for the viewer's labels."""
        out = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": self.pid,
                "tid": 0,
                "args": {"name": self.process_name},
            }
        ]
        if self.process_labels:
            labels = ", ".join(f"{k}={v}" for k, v in self.process_labels.items())
            out.append(
                {
                    "name": "process_labels",
                    "ph": "M",
                    "pid": self.pid,
                    "tid": 0,
                    "args": {"labels": labels, **self.process_labels},
                }
            )
        with self._lock:
            tracks = dict(self._tracks)
        for track, tid in tracks.items():
            out.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": self.pid,
                    "tid": tid,
                    "args": {"name": track},
                }
            )
        return out

    def to_chrome(self) -> dict[str, Any]:
        # Truncation must not be silent: a capped buffer drops the TAIL of
        # the run, and a viewer (or the analysis roll-up) reading a clean-
        # looking file would conclude the instrumented window covered the
        # whole job. The count rides in the document (otherData survives
        # the object format) and is also logged at export time.
        out: dict[str, Any] = {
            "traceEvents": self.metadata_events() + self.events(),
            "displayTimeUnit": "ms",
        }
        if self._dropped:
            out["otherData"] = {
                "dropped_events": {self.process_name: self._dropped}
            }
        return out

    def export(self, path: str | Path) -> Path:
        if self._dropped:
            logger.warning(
                "Tracer %r dropped %d events past the %d-event cap; the "
                "exported timeline is truncated.",
                self.process_name, self._dropped, self._max_events,
            )
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_chrome()), encoding="utf-8")
        return path


def export_chrome_trace(path: str | Path, tracers: Iterable[Tracer]) -> Path:
    """Merge several tracers (master + workers) into one loadable file."""
    events: list[dict[str, Any]] = []
    dropped: dict[str, int] = {}
    for tracer in tracers:
        events.extend(tracer.metadata_events())
        events.extend(tracer.events())
        if tracer.dropped:
            dropped[tracer.process_name] = tracer.dropped
            logger.warning(
                "Tracer %r dropped %d events past its cap; the exported "
                "timeline is truncated.", tracer.process_name, tracer.dropped,
            )
    document: dict[str, Any] = {"traceEvents": events, "displayTimeUnit": "ms"}
    if dropped:
        document["otherData"] = {"dropped_events": dropped}
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(document), encoding="utf-8")
    return path
