"""A worker's start-up as eight exclusive stages (obs/startup.py).

The recorder alone (contiguous, exclusive, in order; gauges and buffered
events after the hand-over), JAX's own compile events as counters and spans,
`TpuRaytraceBackend.warm()` at a small size on the CPU, a deliberately late
compile, one mock-backend job through the real worker and master commands,
the validator's invariant for a stitched start-up, and the benchmark's ten
readers over a scrape.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import pytest

from tpu_render_cluster.obs import (
    STARTUP_STAGES,
    MetricsRegistry,
    Tracer,
    get_registry,
    get_startup,
    validate_trace_document,
    validate_trace_file,
)
from tpu_render_cluster.obs import startup as startup_module
from tpu_render_cluster.obs.prometheus import render_prometheus
from tpu_render_cluster.obs.startup import StartupRecorder

REPO_ROOT = Path(__file__).resolve().parent.parent


def stage_events(tracer: Tracer) -> list[dict]:
    return [e for e in tracer.events() if e["cat"] == "worker.startup"]


def edges(event: dict) -> tuple[float, float]:
    return event["ts"] / 1e6, (event["ts"] + event["dur"]) / 1e6


# -- the recorder alone ------------------------------------------------------------


def test_the_vocabulary_is_eight_fixed_names():
    assert STARTUP_STAGES == (
        "interpreter", "backend_init", "geometry", "program_build",
        "first_execute", "connect", "await_job", "first_frame",
    )


def test_the_process_start_is_the_kernels_not_the_imports():
    started = startup_module.process_start_time()
    assert started is not None
    # before this module was imported, and not before the machine's day began
    assert time.time() - 86400 < started < time.time()
    assert StartupRecorder().process_start == pytest.approx(started, abs=0.02)
    assert StartupRecorder(process_start=12.5).process_start == 12.5


def test_the_stages_are_contiguous_and_add_up_to_finish_less_the_process_start():
    recorder = StartupRecorder()
    tracer = Tracer("worker-under-test")
    recorder.attach(tracer, MetricsRegistry())
    for stage in STARTUP_STAGES[1:]:
        time.sleep(0.003)
        assert recorder.enter(stage)
    time.sleep(0.003)
    assert not recorder.finished
    assert recorder.finish() and recorder.finished
    finished_at = time.time()
    events = stage_events(tracer)
    assert [e["name"] for e in events] == list(STARTUP_STAGES)
    assert edges(events[0])[0] == pytest.approx(recorder.process_start, abs=1e-6)
    for before, after in zip(events, events[1:]):  # a stage begins where the one before it ends
        assert edges(after)[0] == pytest.approx(edges(before)[1], abs=2e-6)
    seconds = recorder.seconds()
    assert all(seconds[stage] >= 0.003 for stage in STARTUP_STAGES)
    assert sum(seconds.values()) == pytest.approx(finished_at - recorder.process_start, abs=0.001)
    assert sum(seconds.values()) == pytest.approx(edges(events[-1])[1] - recorder.process_start, abs=1e-5)
    assert validate_trace_document(tracer.to_chrome()) == []


@pytest.mark.parametrize("stage", ["interpreter", "backend_init", "geometry"])
def test_a_stage_at_or_before_the_open_one_is_refused(stage):
    recorder = StartupRecorder()
    assert recorder.enter("backend_init") and recorder.enter("geometry")
    before = recorder.seconds()
    assert recorder.enter(stage) is False  # another worker of the process set that mark
    assert recorder.seconds() == before
    assert recorder.enter("connect")  # later stages may still be entered, the skipped read 0
    assert recorder.seconds()["program_build"] == recorder.seconds()["first_execute"] == 0.0


def test_an_unknown_stage_is_refused_and_nothing_is_entered_after_finish():
    recorder = StartupRecorder()
    with pytest.raises(ValueError, match="unknown start-up stage"):
        recorder.enter("compositing")
    assert recorder.finish()
    assert recorder.enter("first_frame") is False and recorder.finish() is False


def test_all_eight_gauges_are_exposed_at_the_hand_over_and_unentered_ones_read_0():
    recorder = StartupRecorder()
    recorder.enter("backend_init")
    time.sleep(0.002)
    recorder.enter("connect")  # a worker without --warmScene
    registry = MetricsRegistry()
    assert recorder.attach(Tracer("w"), registry)
    assert recorder.attach(Tracer("second"), MetricsRegistry()) is False  # the first worker wins
    text = render_prometheus(registry.snapshot())
    for stage in STARTUP_STAGES:
        assert f'worker_startup_stage_seconds{{stage="{stage}"}}' in text
    gauge = registry.gauge("worker_startup_stage_seconds", "", labels=("stage",))
    assert gauge.value(stage="interpreter") > 0 and gauge.value(stage="backend_init") >= 0.002
    for stage in ("geometry", "program_build", "first_execute", "connect", "await_job", "first_frame"):
        assert gauge.value(stage=stage) == 0.0  # never entered, or still open
    assert registry.gauge("process_start_time_seconds", "").value() == recorder.process_start
    time.sleep(0.002)
    recorder.enter("await_job")  # written through from now on
    assert gauge.value(stage="connect") >= 0.002


def test_events_buffered_before_a_tracer_exists_arrive_with_cat_track_and_cpu_s():
    recorder = StartupRecorder()
    recorder.enter("backend_init")
    with recorder.child("import_jax"):
        sum(i * i for i in range(200_000))  # CPU the stage consumed
    recorder.span("bvh_build", cat="render", start_wall=time.time() - 0.0004, duration=0.0004,
                  args={"model": "dragon", "triangles": 871200})
    recorder.enter("connect")
    tracer = Tracer("late")
    recorder.attach(tracer, MetricsRegistry())
    events = tracer.events()
    assert [e["name"] for e in events] == [
        "interpreter", "import_jax", "bvh_build", "backend_init",
        "geometry", "program_build", "first_execute",
    ]
    tracks = {m["args"]["name"] for m in tracer.metadata_events() if m["name"] == "thread_name"}
    assert tracks == {"setup"} and len({e["tid"] for e in events}) == 1
    by_name = {e["name"]: e for e in events}
    assert by_name["backend_init"]["cat"] == "worker.startup"
    assert by_name["backend_init"]["args"]["cpu_s"] > 0
    assert by_name["geometry"]["args"] == {"cpu_s": 0.0} and by_name["geometry"]["dur"] == 0
    assert by_name["bvh_build"]["cat"] == "render"
    assert by_name["bvh_build"]["args"] == {"model": "dragon", "triangles": 871200}
    # the child lies inside its stage
    assert edges(by_name["backend_init"])[0] <= edges(by_name["import_jax"])[0]
    assert edges(by_name["import_jax"])[1] <= edges(by_name["backend_init"])[1] + 1e-4
    assert validate_trace_document(tracer.to_chrome()) == []


def test_the_recorder_is_importable_before_jax():
    code = (
        "import sys; from tpu_render_cluster.obs import startup; "
        "startup.get_startup().enter('backend_init'); "
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; print('ok')"
    )
    result = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
    )
    assert result.returncode == 0 and result.stdout.strip() == "ok", result.stderr


def test_a_process_that_never_asks_carries_no_recorder():
    """The master and the CLIs import `obs` and read nothing of `/proc`:
    the recorder is made when a worker first asks for it."""
    code = (
        "from tpu_render_cluster import obs; from tpu_render_cluster.obs import startup; "
        "assert startup._recorder is None; "
        "first = obs.get_startup(); assert first is obs.get_startup() is startup._recorder; "
        "startup.reset_startup(); assert startup._recorder is None; "
        "assert obs.get_startup() is not first; print('ok')"
    )
    result = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
    )
    assert result.returncode == 0 and result.stdout.strip() == "ok", result.stderr


def test_without_the_kernels_start_time_the_stages_count_from_the_first_ask(monkeypatch):
    """Where `/proc/self/stat` cannot say, `interpreter` begins when the
    recorder is first asked for, which a worker does as `main` is entered."""
    monkeypatch.setattr(startup_module, "process_start_time", lambda: None)
    before = time.time()
    recorder = get_startup()
    assert before <= recorder.process_start <= time.time()
    recorder.enter("backend_init")
    assert 0.0 <= recorder.seconds()["interpreter"] < 0.05


# -- the validator ------------------------------------------------------------------


def startup_document(stages: list[tuple[str, float, float]], pid: int = 7) -> dict:
    return {"traceEvents": [
        {"name": name, "cat": "worker.startup", "ph": "X", "pid": pid, "tid": 1,
         "ts": start * 1e6, "dur": seconds * 1e6}
        for name, start, seconds in stages
    ]}


@pytest.mark.parametrize("stages, problem", [
    ([("interpreter", 10.0, 1.0), ("backend_init", 11.0, 2.0), ("connect", 13.0, 0.5)], None),
    ([("interpreter", 10.0, 1.0), ("backend_init", 11.0005, 2.0)], None),  # under a millisecond
    ([("interpreter", 10.0, 1.0), ("backend_init", 11.002, 2.0)], "share an edge"),
    ([("interpreter", 10.0, 1.0), ("backend_init", 10.9, 2.0)], "overlap"),
    ([("backend_init", 10.0, 1.0), ("interpreter", 11.0, 1.0)], "out of STARTUP_STAGES order"),
    ([("interpreter", 10.0, 1.0), ("interpreter", 11.0, 1.0)], "out of STARTUP_STAGES order"),
    ([("interpreter", 10.0, 1.0), ("warm", 11.0, 1.0)], "unknown start-up stage"),
])
def test_the_validator_refuses_a_start_up_that_was_stitched_wrongly(stages, problem):
    problems = validate_trace_document(startup_document(stages))
    if problem is None:
        assert problems == []
    else:
        assert len(problems) == 1 and problem in problems[0]


def test_the_validator_holds_each_process_to_its_own_start_up():
    document = startup_document([("interpreter", 10.0, 1.0), ("backend_init", 11.0, 1.0)], pid=1)
    document["traceEvents"] += startup_document([("interpreter", 10.5, 1.0)], pid=2)["traceEvents"]
    assert validate_trace_document(document) == []


# -- JAX's own account ----------------------------------------------------------------


def compile_counts() -> dict[str, tuple[float, float]]:
    registry = get_registry()
    seconds = registry.counter("render_jax_compile_seconds_total", "", labels=("phase",))
    events = registry.counter("render_jax_compile_events_total", "", labels=("phase",))
    return {
        phase: (events.value(phase=phase), seconds.value(phase=phase))
        for phase in ("trace", "lower", "backend_compile")
    }


def test_a_jit_raises_each_phase_once_and_a_second_call_raises_nothing(startup_timeline, monkeypatch):
    import jax
    import jax.numpy as jnp

    startup_module.watch_jax_compiles()
    startup_module.watch_jax_compiles()  # once a process, whoever asks again
    monkeypatch.setattr(startup_module, "COMPILE_SPAN_FLOOR_SECONDS", 0.0)

    def thrice_and_one(x):  # lax alone: a jnp helper is a jit of its own, traced inside
        return jax.lax.add(jax.lax.mul(x, x), x)

    jitted = jax.jit(thrice_and_one)
    x = jnp.arange(37.0)
    before = compile_counts()
    jitted(x).block_until_ready()
    first = compile_counts()
    for phase in ("trace", "lower", "backend_compile"):
        assert first[phase][0] - before[phase][0] == 1, phase
        assert first[phase][1] > before[phase][1], phase
    jitted(x).block_until_ready()
    assert compile_counts() == first
    spans = [
        e for e in startup_timeline.events()
        if e["cat"] == "render.compile" and "thrice_and_one" in e["args"]["fun_name"]
    ]  # the argument's own little programs are on the timeline too, at a floor of 0
    assert [e["name"] for e in spans] == ["trace", "lower", "backend_compile"]
    assert [e["args"]["fun_name"] for e in spans] == [
        "thrice_and_one", "jit(thrice_and_one)", "jit(thrice_and_one)"
    ]
    tracks = {m["tid"]: m["args"]["name"] for m in startup_timeline.metadata_events() if m["name"] == "thread_name"}
    assert {tracks[e["tid"]] for e in spans} == {"compile"}


def test_a_phase_inside_a_phase_is_counted_once_and_an_event_under_10_ms_makes_no_span(startup_timeline):
    startup_module.watch_jax_compiles()
    trace, lower = "/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/jaxpr_to_mlir_module_duration"
    before = compile_counts()
    now = time.time()
    # JAX says "begun" with a scalar and "ended" with a time span: an outer
    # trace of 100 ms that holds a helper's trace of 30 ms and one of 5 ms
    startup_module._on_compile_phase_entered(trace, now, fun_name="frame")
    startup_module._on_compile_phase_entered(trace, now + 0.010, fun_name="helper")
    startup_module._on_compile_phase(trace, now + 0.010, now + 0.040, fun_name="helper")
    startup_module._on_compile_phase_entered(trace, now + 0.050, fun_name="clip")
    startup_module._on_compile_phase(trace, now + 0.050, now + 0.055, fun_name="clip")
    startup_module._on_compile_phase(trace, now, now + 0.100, fun_name="frame")
    startup_module._on_compile_phase(lower, now + 0.100, now + 0.109, fun_name="jit(frame)")
    startup_module._on_compile_phase("/jax/some/other_duration", now, now + 5.0)
    after = compile_counts()
    assert after["trace"][0] - before["trace"][0] == 3
    assert after["trace"][1] - before["trace"][1] == pytest.approx(0.100, abs=1e-6)  # not 0.135
    assert after["lower"][1] - before["lower"][1] == pytest.approx(0.009, abs=1e-6)
    assert after["backend_compile"] == before["backend_compile"]
    spans = [(e["name"], e["args"]["fun_name"]) for e in startup_timeline.events() if e["cat"] == "render.compile"]
    assert spans == [("trace", "helper"), ("trace", "frame")]  # 5 ms and 9 ms: counted, not drawn


def test_cache_hits_misses_and_their_seconds_are_counted():
    from jax import monitoring

    startup_module.watch_jax_compiles()
    registry = get_registry()
    requests = registry.counter("render_compile_cache_requests_total", "", labels=("result",))
    retrieval = registry.counter("render_compile_cache_retrieval_seconds_total", "")
    saved = registry.counter("render_compile_cache_saved_seconds_total", "")
    before = (requests.value(result="hit"), requests.value(result="miss"), retrieval.value(), saved.value())
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/tasks_using_cache")  # not ours
    monitoring.record_event_duration_secs("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    monitoring.record_event_duration_secs("/jax/compilation_cache/compile_time_saved_sec", 3.5)
    # a retrieval slower than the compile it replaced: nothing saved, and a counter only goes up
    monitoring.record_event_duration_secs("/jax/compilation_cache/compile_time_saved_sec", -0.5)
    after = (requests.value(result="hit"), requests.value(result="miss"), retrieval.value(), saved.value())
    assert [b - a for a, b in zip(before, after)] == pytest.approx([2, 1, 0.25, 3.5])
    text = render_prometheus(registry.snapshot())
    assert 'render_compile_cache_requests_total{result="hit"}' in text
    assert 'render_jax_compile_seconds_total{phase="backend_compile"}' in text


# -- the backend ----------------------------------------------------------------------


def make_job(name: str, tmp_path: Path):
    from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy

    return BlenderJob(
        job_name=name, job_description=None, project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py", frame_range_from=1, frame_range_to=4,
        wait_for_number_of_workers=1, frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/frames", output_file_name_format="rendered-######",
        output_file_format="JPEG",
    )


def test_warm_fills_its_three_stages_in_order_with_the_bvh_builds_inside_geometry(startup_timeline, tmp_path):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    recorder = get_startup()
    recorder.enter("backend_init")
    backend = TpuRaytraceBackend(base_directory=tmp_path, width=16, height=16, samples=1, max_bounces=2)
    backend.warm("02_physics-mesh_measuring")
    recorder.enter("connect")  # as worker.main does when warm() has returned
    assert not hasattr(backend, "bvh_builds")
    seconds = recorder.seconds()
    assert seconds["geometry"] > 0 and seconds["program_build"] > 0 and seconds["first_execute"] > 0
    by_name = {e["name"]: e for e in startup_timeline.events()}
    stages = [e["name"] for e in stage_events(startup_timeline)]
    assert stages == list(STARTUP_STAGES[:5])
    build = by_name["bvh_build"]
    assert build["cat"] == "render" and build["args"] == {"model": "box", "triangles": 12}
    assert edges(by_name["geometry"])[0] <= edges(build)[0] and edges(build)[1] <= edges(by_name["geometry"])[1]
    opened = by_name["open_device"]
    assert edges(by_name["backend_init"])[0] <= edges(opened)[0] and edges(opened)[1] <= edges(by_name["backend_init"])[1]
    # the program's build holds JAX's phases, named
    inside = edges(by_name["program_build"])
    compiles = [e for e in startup_timeline.events() if e["cat"] == "render.compile"]
    assert {e["name"] for e in compiles} == {"trace", "lower", "backend_compile"}
    assert any(e["args"]["fun_name"] == "jit(program)" for e in compiles)
    for event in compiles:
        assert inside[0] - 1e-3 <= edges(event)[0] and edges(event)[1] <= inside[1] + 1e-3, event
    gauge = get_registry().gauge("render_bvh_build_seconds", "", labels=("model",))
    assert gauge.value(model="box") == pytest.approx(build["dur"] / 1e6, abs=1e-6)
    assert validate_trace_document(startup_timeline.to_chrome()) == []


def test_a_second_shape_after_the_first_frame_shows_as_named_compile_spans(startup_timeline, tmp_path):
    from tpu_render_cluster.worker.backends.tpu_raytrace import TpuRaytraceBackend

    job = make_job("04_very-simple_late", tmp_path)
    TpuRaytraceBackend(base_directory=tmp_path, width=16, height=16, samples=1, max_bounces=2)._render_sync(job, 1)
    get_startup().finish()  # the first frame is on disk: what follows is a late compile
    first_frame_done = time.time()
    before = compile_counts()
    TpuRaytraceBackend(base_directory=tmp_path, width=24, height=16, samples=1, max_bounces=2)._render_sync(job, 2)
    after = compile_counts()
    for phase in ("trace", "lower", "backend_compile"):
        assert after[phase][0] > before[phase][0] and after[phase][1] > before[phase][1], phase
    late = [
        e for e in startup_timeline.events()
        if e["cat"] == "render.compile" and e["ts"] / 1e6 >= first_frame_done
    ]
    assert {"trace", "lower", "backend_compile"} <= {e["name"] for e in late}
    assert {e["args"]["fun_name"] for e in late if e["name"] != "trace"} >= {"jit(program)"}
    assert any(e["name"] == "trace" and e["args"]["fun_name"] == "program" for e in late)


# -- one job through the real commands ----------------------------------------------------


def free_port() -> int:
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        return probe.getsockname()[1]


def scrape_stage_gauges(port: int) -> dict[str, float]:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5) as reply:
        text = reply.read().decode()
    found = {}
    for line in text.splitlines():
        if line.startswith(("worker_startup_stage_seconds{", "process_start_time_seconds ")):
            name, value = line.rsplit(" ", 1)
            found[name] = float(value)
    return found


def test_a_mock_job_exports_eight_stages_that_add_up_and_serves_the_gauges(tmp_path, kill_leftover_children):
    frames = 120
    job_path = tmp_path / "job.toml"
    job_path.write_text(f'''
job_name = "startup-mock"
job_description = "start-up stages, mock backend"
project_file_path = "%BASE%/p.blend"
render_script_path = "%BASE%/s.py"
frame_range_from = 1
frame_range_to = {frames}
wait_for_number_of_workers = 1
output_directory_path = "%BASE%/frames"
output_file_name_format = "rendered-####"
output_file_format = "PNG"

[frame_distribution_strategy]
strategy_type = "eager-naive-coarse"
target_queue_size = 4
''')
    port, telemetry = free_port(), free_port()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO_ROOT)}
    master = subprocess.Popen(
        [sys.executable, "-m", "tpu_render_cluster.master.main", "--host", "127.0.0.1",
         "--port", str(port), "run-job", str(job_path), "--resultsDirectory", str(tmp_path / "results")],
        env=env, cwd=REPO_ROOT,
    )
    worker = subprocess.Popen(
        [sys.executable, "-m", "tpu_render_cluster.worker.main", "--masterServerHost", "127.0.0.1",
         "--masterServerPort", str(port), "--baseDirectory", str(tmp_path), "--backend", "mock",
         "--telemetryPort", str(telemetry), "--telemetryHost", "127.0.0.1"],
        env=env, cwd=REPO_ROOT,
    )
    served: dict[str, float] = {}
    deadline = time.monotonic() + 120
    while worker.poll() is None and time.monotonic() < deadline:
        try:
            served = scrape_stage_gauges(telemetry)
        except OSError:
            served = {}
        if served.get('worker_startup_stage_seconds{stage="first_frame"}', 0.0) > 0:
            break
        time.sleep(0.02)
    assert master.wait(timeout=180) == 0
    assert worker.wait(timeout=60) == 0
    # /metrics, while the job ran: all eight, the warm stages 0 without --warmScene
    assert len(served) == 9, served
    for stage in ("geometry", "program_build", "first_execute"):
        assert served[f'worker_startup_stage_seconds{{stage="{stage}"}}'] == 0.0
    for stage in ("interpreter", "backend_init", "connect", "await_job", "first_frame"):
        assert served[f'worker_startup_stage_seconds{{stage="{stage}"}}'] > 0.0, stage
    process_start = served["process_start_time_seconds"]

    (timeline,) = (tmp_path / "obs").glob("worker-*_trace-events.json")
    assert validate_trace_file(timeline) == []
    events = json.loads(timeline.read_text())["traceEvents"]
    stages = [e for e in events if e.get("cat") == "worker.startup"]
    assert [e["name"] for e in stages] == list(STARTUP_STAGES)
    assert all("cpu_s" in e["args"] for e in stages)
    assert edges(stages[0])[0] == pytest.approx(process_start, abs=1e-5)
    finished_at = edges(stages[-1])[1]
    assert sum(e["dur"] for e in stages) / 1e6 == pytest.approx(finished_at - process_start, abs=0.05)
    # the first frame's own spans end where the last stage does
    first_write = min(
        (e for e in events if e.get("cat") == "worker" and e["name"] == "write"), key=lambda e: e["ts"]
    )
    assert finished_at == pytest.approx(edges(first_write)[1], abs=0.05)
    for stage, served_seconds in served.items():
        if stage.startswith("worker_startup"):
            name = stage.split('"')[1]
            assert served_seconds == pytest.approx(next(e for e in stages if e["name"] == name)["dur"] / 1e6, abs=1e-5)
    (snapshot,) = (tmp_path / "obs").glob("worker-*_metrics.json")
    assert "worker_startup_stage_seconds" in snapshot.read_text()


def test_the_first_worker_of_a_harness_process_carries_the_stages(tmp_path):
    from tpu_render_cluster.harness.local import run_and_persist
    from tpu_render_cluster.worker.backends.mock import MockBackend

    job = make_job("startup-harness", tmp_path)
    raw = run_and_persist(job, [MockBackend(), MockBackend()], tmp_path / "results")
    (timeline,) = [p for p in raw.parent.glob("*_trace-events.json") if "cluster" not in p.name]
    assert validate_trace_file(timeline) == []
    stages = [e for e in json.loads(timeline.read_text())["traceEvents"] if e.get("cat") == "worker.startup"]
    assert [e["name"] for e in stages] == list(STARTUP_STAGES)  # one worker's, not two
    assert len({e["pid"] for e in stages}) == 1 and get_startup().finished


# -- the benchmark's readers ----------------------------------------------------------------


def worker_scrape(first_frame: float, connect: float, hits: int, misses: int):
    from benchmark.lib import scrape

    registry = MetricsRegistry()
    stage = registry.gauge("worker_startup_stage_seconds", "", labels=("stage",))
    for name, seconds in zip(STARTUP_STAGES, (1.5, 9.0, 2.5, 11.0, 0.5, connect, 3.0, first_frame)):
        stage.set(seconds, stage=name)
    phase = registry.counter("render_jax_compile_seconds_total", "", labels=("phase",))
    for name, seconds in (("trace", 4.0), ("lower", 3.0 + connect), ("backend_compile", 2.0)):
        phase.inc(seconds, phase=name)
    requests = registry.counter("render_compile_cache_requests_total", "", labels=("result",))
    requests.inc(hits, result="hit")
    requests.inc(misses, result="miss")
    return scrape.parse(render_prometheus(registry.snapshot()))


@pytest.mark.parametrize("metric, value", [
    ("startup_interpreter_s", 1.5),
    ("startup_backend_init_s", 9.0),
    ("startup_geometry_s", 2.5),
    ("startup_program_build_s", 11.0),
    ("startup_first_execute_s", 0.5),
    ("startup_join_s", 3.25),  # the smallest connect + await_job: the last to connect waited for nobody
    ("startup_first_frame_s", 0.75),  # every other: the slowest worker's
    ("startup_lower_s", 8.0),
    ("startup_backend_compile_s", 2.0),
    ("compile_cache_hit_share", 75.0),
])
def test_the_benchmarks_readers_over_two_workers_scrapes(metric, value):
    from benchmark.lib import manifest, readers

    after = [worker_scrape(0.25, 1.0, 3, 1), worker_scrape(0.75, 0.25, 3, 1)]
    run = {"scrapes": {"workers": ([{}, {}], after), "master": ([{}], [{}])}}
    assert readers.read_metric(metric, run) == pytest.approx(value)
    entry = next(m for m in manifest.load_benchmark()["per_layer"] if m["name"] == metric)
    assert entry["moves"] == "setup_s" and "workloads" not in entry  # reads in all six cells
    # a program from before the series (the parent): nothing to read, and no raise
    assert readers.read_metric(metric, {"scrapes": {"workers": ([{}], [{}])}}) is None


def test_a_cache_nobody_asked_reads_0_where_the_series_exists():
    from benchmark.lib import readers

    run = {"scrapes": {"workers": ([{}], [worker_scrape(0.25, 1.0, 0, 0)])}}
    assert readers.read_metric("compile_cache_hit_share", run) == 0.0
