"""A worker's start-up, from exec to its first frame on disk.

Eight stages, the same for every backend (``STARTUP_STAGES``), EXCLUSIVE
and CONTIGUOUS: a stage begins where the one before it ends, so from the
process's start to its first whole frame file exactly one is open at every
instant and the stages add up to the worker's own part of set-up. The API
is marks: ``enter(stage)`` closes the open stage, ``finish()`` closes the
last; a stage never entered reads 0.

    interpreter    the kernel's start time of the process -> worker.main.main
                   entered: Python's start and the package's imports
    backend_init   -> the backend object exists: compile-cache set-up,
                   ``import jax`` (child span ``import_jax``), the device
                   opened and claimed (child span ``open_device``)
    geometry       -> ``_build_geometry`` returns: mesh generation, BLAS
                   builds (child spans ``bvh_build``), join, upload
    program_build  -> the first call of the frame's program has returned
                   (dispatch is asynchronous: the executable exists and the
                   work is queued): trace, lowering, XLA compile or its
                   load from the persistent cache, the profiler's capture
    first_execute  -> the warm frame's pixels are on the host
    connect        -> the handshake has succeeded: ``Worker(...)``, the
                   telemetry server, connect with back-off, handshake
    await_job      -> the first frame is in this worker's queue: the master
                   waits for every worker, starts the job, assigns
    first_frame    -> the first frame's file is renamed into place

Without ``--warmScene`` the three middle stages stay empty, unless a job
is prepared when it is announced (a scheduler service sends the job with
``event_job-started``): what the preparations that ended before the first
frame was queued spent on geometry, the program and its first execute is
CREDITED to the three (``credit``) and taken out of ``await_job``, inside
which it was spent, so the eight still add up; on the timeline
``await_job`` stays one whole span with the ``job_prepare`` spans inside
it. A worker whose master announces no job pays all of it in
``first_frame``.

The recorder is process-scoped like ``get_registry()`` (where several
workers share a process, as in the in-process harness, the first to reach
a mark sets it), stdlib only and importable before JAX. It buffers until
the worker's ``Tracer`` exists (``attach``), hands over what it holds, and
writes through from then on. A stage is one ``worker_startup_stage_seconds
{stage}`` gauge and one complete event ``cat: "worker.startup"`` on track
``setup`` with ``args.cpu_s``, the process CPU seconds the stage consumed:
a stage that is wall without CPU waited (for the chip, the disk, the
master). Stage edges are read on the wall clock alone: it is the clock the
process's start is known on, and an edge read once cannot leave a gap.

``watch_jax_compiles()`` puts JAX's own account of every program it builds
(``jax.monitoring``: trace, lower, backend compile, persistent-cache hits
and misses) into the process registry, and each phase of 10 ms or more on
the timeline as a ``cat: "render.compile"`` span through the same buffer:
at start-up and after it, so a compile inside a job is a named span.
Inside ``compile_span_args(**args)`` those spans carry ``args`` as well:
the backend says there which trace kernel the program in hand holds.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator, Mapping

__all__ = [
    "COMPILE_SPAN_FLOOR_SECONDS",
    "STARTUP_STAGES",
    "StartupRecorder",
    "compile_span_args",
    "get_startup",
    "process_start_time",
    "reset_startup",
    "watch_jax_compiles",
]

STARTUP_STAGES = (
    "interpreter",
    "backend_init",
    "geometry",
    "program_build",
    "first_execute",
    "connect",
    "await_job",
    "first_frame",
)

# A process that never makes a Worker (render.cli, a test) still buffers
# its compile spans; the newest are dropped past this.
_MAX_BUFFERED = 4096

# The eager one-liners of start-up stay out of the timeline; the counters
# take every event.
COMPILE_SPAN_FLOOR_SECONDS = 0.010


def process_start_time() -> float | None:
    """When the kernel started this process, in wall-clock seconds; None
    where ``/proc/self/stat`` cannot say."""
    try:
        with open("/proc/self/stat", "rb") as stat:
            text = stat.read()
        # starttime is field 22, in clock ticks since boot; comm (field 2)
        # may hold spaces and brackets, so count from its closing one.
        ticks = int(text[text.rindex(b")") + 2 :].split()[19])
        since_boot = ticks / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - since_boot
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.time() - age if age >= 0 else None


class StartupRecorder:
    """The stages of one process's start-up, and the buffer in front of
    the worker's tracer that their spans and their children's ride."""

    def __init__(self, process_start: float | None = None) -> None:
        # where the kernel's start time cannot be read, the stages count
        # from here: get_startup() first runs as worker.main.main is entered
        now = time.time()
        if process_start is None:
            process_start = process_start_time()
        if process_start is None or process_start > now:
            process_start = now
        self.process_start = process_start
        self._lock = threading.RLock()
        # index of the open stage; len(STARTUP_STAGES) once finished
        self._open = 0
        self._since = process_start
        self._cpu_since = 0.0
        self._seconds: dict[str, float] = {}
        # seconds of the open stage that credit() gave to earlier ones
        self._credited = 0.0
        self._buffer: list[dict[str, Any]] = []
        self._tracer = None
        self._gauge = None

    # -- marks ---------------------------------------------------------------

    @property
    def finished(self) -> bool:
        return self._open >= len(STARTUP_STAGES)

    def enter(self, stage: str) -> bool:
        """Close the open stage and open ``stage``. Stages are entered in
        order only: one at or before the open stage is refused (False,
        nothing changes: another worker of this process set that mark), an
        unknown one raises."""
        try:
            index = STARTUP_STAGES.index(stage)
        except ValueError:
            raise ValueError(
                f"unknown start-up stage {stage!r} (have: {STARTUP_STAGES})"
            ) from None
        return self._advance(index)

    def finish(self) -> bool:
        """Close the last stage: the first frame's file is in place."""
        return self._advance(len(STARTUP_STAGES))

    def _advance(self, index: int) -> bool:
        with self._lock:
            if index <= self._open:
                return False
            now, cpu = time.time(), time.process_time()
            self._close(STARTUP_STAGES[self._open], self._since, now, cpu - self._cpu_since)
            for skipped in STARTUP_STAGES[self._open + 1 : index]:
                self._close(skipped, now, now, 0.0)
            self._open, self._since, self._cpu_since = index, now, cpu
            self._credited = 0.0
            return True

    def credit(self, stage: str, seconds: float) -> bool:
        """Give ``seconds`` of the open stage to ``stage``, a closed one
        before it: work of that stage's kind done late (a job prepared
        while its first frame is awaited). The closed stage's gauge rises
        and the open stage will read that much less when it closes, so the
        stages stay exclusive and add up; the spans are not moved. Refused
        (False) once the first frame is queued: from then on a preparation
        is another job's, beside frames that are landing."""
        index = STARTUP_STAGES.index(stage)
        with self._lock:
            if index >= self._open or self._open >= STARTUP_STAGES.index("first_frame"):
                return False
            seconds = max(0.0, seconds)
            self._credited += seconds
            self._seconds[stage] = self._seconds.get(stage, 0.0) + seconds
            if self._gauge is not None:
                self._gauge.set(self._seconds[stage], stage=stage)
            return True

    def _close(self, stage: str, start: float, end: float, cpu_s: float) -> None:
        seconds = max(0.0, end - start)
        # the gauge is exclusive: less what was credited to earlier stages
        self._seconds[stage] = max(0.0, seconds - self._credited)
        if self._gauge is not None:
            self._gauge.set(self._seconds[stage], stage=stage)
        self.span(
            stage, cat="worker.startup", start_wall=start, duration=seconds,
            args={"cpu_s": round(cpu_s, 6)},
        )

    def seconds(self) -> dict[str, float]:
        """Every stage's seconds; one that is open or was never entered
        reads 0."""
        with self._lock:
            return {stage: self._seconds.get(stage, 0.0) for stage in STARTUP_STAGES}

    # -- spans beneath the stages ----------------------------------------------

    def span(
        self,
        name: str,
        *,
        cat: str,
        start_wall: float,
        duration: float,
        track: str = "setup",
        args: Mapping[str, Any] | None = None,
    ) -> None:
        """One finished span for the worker's timeline: buffered until a
        tracer is attached, written through afterwards."""
        event = dict(
            name=name, cat=cat, start_wall=start_wall, duration=duration,
            track=track, args=args,
        )
        with self._lock:
            if self._tracer is not None:
                self._tracer.complete(**event)
            elif len(self._buffer) < _MAX_BUFFERED:
                self._buffer.append(event)

    @contextmanager
    def child(self, name: str, **args: Any) -> Iterator[None]:
        """Time a part of the open stage as a child span on its track."""
        start_wall, start = time.time(), time.perf_counter()
        try:
            yield
        finally:
            self.span(
                name, cat="worker.setup", start_wall=start_wall,
                duration=time.perf_counter() - start, args=args or None,
            )

    # -- hand-over -------------------------------------------------------------

    def attach(self, tracer, registry) -> bool:
        """The worker's tracer and registry exist: hand over what was
        buffered, expose all eight gauges (0 until their stage closes) and
        ``process_start_time_seconds``, and write through from now on. The
        first worker of a process wins; later ones are refused."""
        with self._lock:
            if self._tracer is not None:
                return False
            self._tracer = tracer
            self._gauge = registry.gauge(
                "worker_startup_stage_seconds",
                "Seconds of each exclusive stage of this process's start-up, "
                "from the kernel's start of the process to the first frame "
                "file in place (0: not entered, or still open)",
                labels=("stage",),
            )
            for stage, seconds in self.seconds().items():
                self._gauge.set(seconds, stage=stage)
            registry.gauge(
                "process_start_time_seconds",
                "Start time of the process since the Unix epoch, in seconds",
            ).set(self.process_start)
            for event in self._buffer:
                tracer.complete(**event)
            self._buffer.clear()
            return True


_recorder: StartupRecorder | None = None
_recorder_lock = threading.Lock()


def get_startup() -> StartupRecorder:
    """The process's start-up recorder, made when first asked for: a
    worker asks as ``main`` is entered, so where the kernel's start time
    cannot be read the stages count from there, and a process that never
    asks (the master, a CLI without JAX) carries none."""
    global _recorder
    with _recorder_lock:
        if _recorder is None:
            _recorder = StartupRecorder()
        return _recorder


def reset_startup() -> None:
    """Drop this process's recorder; the next ``get_startup()`` makes a
    new one, nothing entered and no tracer attached. For tests, whose
    workers share one process."""
    global _recorder
    with _recorder_lock:
        _recorder = None


# -- JAX's own account of the programs it builds ----------------------------------

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_REQUESTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

# name -> (help, labels): the process registry's series JAX's events feed.
_SERIES = {
    "render_jax_compile_seconds_total": (
        "Seconds JAX spent building programs, by phase: trace (Python to a "
        "jaxpr), lower (jaxpr to MLIR, Pallas kernels' Mosaic lowering "
        "included), backend_compile (XLA, or the load from the persistent "
        "cache); each phase's own seconds, without the phases inside it",
        ("phase",),
    ),
    "render_jax_compile_events_total": (
        "Times JAX went through a phase of building a program",
        ("phase",),
    ),
    "render_compile_cache_requests_total": (
        "Executables asked of the persistent compilation cache, by result "
        "(a miss is counted when its entry is written)",
        ("result",),
    ),
    "render_compile_cache_retrieval_seconds_total": (
        "Seconds spent reading executables back from the persistent "
        "compilation cache",
        (),
    ),
    "render_compile_cache_saved_seconds_total": (
        "Compile seconds the persistent cache's hits saved: each entry's "
        "recorded compile time less its retrieval, as JAX reckons it",
        (),
    ),
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "render_compile_cache_retrieval_seconds_total",
    "/jax/compilation_cache/compile_time_saved_sec": "render_compile_cache_saved_seconds_total",
}


def _counter(name: str):
    # asked of the registry each time: a test swaps the process's registry
    from tpu_render_cluster.obs import get_registry

    help_text, labels = _SERIES[name]
    return get_registry().counter(name, help_text, labels=labels)


_open_phases = threading.local()
_span_args = threading.local()


@contextmanager
def compile_span_args(**args: Any) -> Iterator[None]:
    """Whatever JAX builds on this thread inside carries ``args`` on its
    ``render.compile`` spans, beside ``fun_name``: what the caller knows
    of the program and JAX's event does not."""
    previous = getattr(_span_args, "args", {})
    _span_args.args = {**previous, **args}
    try:
        yield
    finally:
        _span_args.args = previous


def _on_compile_phase_entered(event: str, value: float, **kwargs: Any) -> None:
    """JAX says a phase has begun (its start time as a scalar): open a
    frame for what its children will take."""
    if event in _COMPILE_PHASES:
        stack = getattr(_open_phases, "stack", None)
        if stack is None:
            stack = _open_phases.stack = []
        stack.append(0.0)


def _on_compile_phase(event: str, start_time: float, end_time: float, **kwargs: Any) -> None:
    """A phase has ended. The counter takes its own seconds, without the
    phases that ran inside it (a jitted helper traced inside the frame's
    trace, an eager compile inside a lowering), so the phases add up to
    wall time and none is counted twice; the span shows it whole."""
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    seconds = max(0.0, end_time - start_time)
    stack = getattr(_open_phases, "stack", None)
    inside = stack.pop() if stack else 0.0
    if stack:
        stack[-1] += seconds
    _counter("render_jax_compile_seconds_total").inc(max(0.0, seconds - inside), phase=phase)
    _counter("render_jax_compile_events_total").inc(phase=phase)
    if seconds >= COMPILE_SPAN_FLOOR_SECONDS:
        # JAX reads these edges on time.time(), the clock Tracer anchors on.
        get_startup().span(
            phase, cat="render.compile", track="compile",
            start_wall=start_time, duration=seconds,
            args={
                "fun_name": str(kwargs.get("fun_name", "")),
                **getattr(_span_args, "args", {}),
            },
        )


def _on_cache_seconds(event: str, duration_secs: float, **kwargs: Any) -> None:
    series = _CACHE_SECONDS.get(event)
    if series is not None:
        # a retrieval slower than the compile it replaced saved nothing
        _counter(series).inc(max(0.0, duration_secs))


def _on_cache_request(event: str, **kwargs: Any) -> None:
    result = _CACHE_REQUESTS.get(event)
    if result is not None:
        _counter("render_compile_cache_requests_total").inc(result=result)


_watching = False
_watch_lock = threading.Lock()


def watch_jax_compiles() -> None:
    """Register the listeners with ``jax.monitoring``, once a process
    (JAX keeps a listener for good), and expose every series at 0: a
    scrape that finds none could not tell "nothing was built" from "not
    counted". A listener runs only when JAX builds something."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        _watching = True
    from jax import monitoring

    for phase in _COMPILE_PHASES.values():
        _counter("render_jax_compile_seconds_total").inc(0.0, phase=phase)
        _counter("render_jax_compile_events_total").inc(0.0, phase=phase)
    for result in _CACHE_REQUESTS.values():
        _counter("render_compile_cache_requests_total").inc(0.0, result=result)
    for series in _CACHE_SECONDS.values():
        _counter(series).inc(0.0)
    monitoring.register_scalar_listener(_on_compile_phase_entered)
    monitoring.register_event_time_span_listener(_on_compile_phase)
    monitoring.register_event_duration_secs_listener(_on_cache_seconds)
    monitoring.register_event_listener(_on_cache_request)
