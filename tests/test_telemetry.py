"""Live telemetry plane suite (obs/prometheus, obs/http, obs/dashboard,
obs/slo).

Fast deterministic tier-1 subset (marked ``telemetry``):

- exposition units: metric/label lint, render layout (cumulative
  ``_bucket`` + ``+Inf`` + ``_sum``/``_count``), label-value escaping
  round-trip, the whole-codebase metric-name lint;
- endpoints: content types, /healthz, /clusterz, 404/405, plus an e2e
  2-worker harness run scraped MID-JOB over real HTTP;
- dashboard: histogram-quantile reconstruction and the pure renderer;
- SLO engine: burn math, exactly-once fire/clear edges, deadline
  one-shot, TOML declaration, a deterministic breach-and-recovery e2e,
  and a seeded straggler chaos run driving a declared objective into
  burn with the full invariant audit still green;
- the statistics.json ``slo`` fold, and the run-job CLI's crash-path
  artifact export.
"""

from __future__ import annotations

import asyncio
import json
import re
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import pytest

from tpu_render_cluster.jobs.models import (
    BlenderJob,
    DistributionStrategy,
    JobSlo,
)
from tpu_render_cluster.obs.dashboard import (
    histogram_quantiles,
    render_dashboard,
)
from tpu_render_cluster.obs.http import TelemetryServer
from tpu_render_cluster.obs.prometheus import (
    CONTENT_TYPE,
    lint_metric,
    lint_snapshot,
    parse_prometheus,
    render_prometheus,
)
from tpu_render_cluster.obs.registry import MetricsRegistry
from tpu_render_cluster.obs.slo import (
    KIND_DEADLINE,
    KIND_UNIT_LATENCY,
    SloService,
    SloTracker,
)
from tpu_render_cluster.obs.tracer import Tracer

pytestmark = pytest.mark.telemetry

REPO_ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Exposition format + lint


def test_lint_metric_conventions():
    assert lint_metric("transport_bytes_total", "counter", ("worker",)) == []
    assert lint_metric("master_worker_queue_depth", "gauge", ()) == []
    assert lint_metric("worker_frame_phase_seconds", "histogram", ("phase",)) == []
    # Counters must end _total.
    assert lint_metric("transport_bytes", "counter", ())
    # Gauges/histograms must not claim counter or expansion suffixes...
    assert lint_metric("queue_total", "gauge", ())
    assert lint_metric("queue_count", "gauge", ())
    assert lint_metric("latency_bucket", "histogram", ())
    # ...and must end in a unit suffix.
    assert lint_metric("master_queue", "gauge", ())
    # Name/label grammar.
    assert lint_metric("Bad-Name_total", "counter", ())
    assert lint_metric("ok_total", "counter", ("Bad-Label",))
    assert lint_metric("mystery_seconds", "summary", ())


_METRIC_CALL_RE = re.compile(
    r'\.(counter|gauge|histogram)\(\s*\n?\s*(?:name=)?(["\'])([a-z0-9_]+)\2',
    re.M,
)
_ANOMALY_CALL_RE = re.compile(r'_count_anomaly\(\s*\n?\s*(["\'])([a-z0-9_]+)\1', re.M)


def test_every_registered_metric_name_is_lint_clean():
    """The whole-codebase lint: every name/kind a source file registers
    must satisfy the exposition conventions, so the /metrics exporter
    (which refuses non-conforming series) can never 500 on a production
    registry."""
    registered: dict[tuple[str, str], str] = {}
    sources = list((REPO_ROOT / "tpu_render_cluster").rglob("*.py"))
    for path in sources:
        text = path.read_text(encoding="utf-8")
        for match in _METRIC_CALL_RE.finditer(text):
            registered[(match.group(1), match.group(3))] = str(path)
        for match in _ANOMALY_CALL_RE.finditer(text):
            registered[("counter", match.group(2))] = str(path)
    # Guard against the scan regex rotting into a no-op.
    assert len(registered) > 45, sorted(registered)
    problems = []
    for (kind, name), path in sorted(registered.items()):
        for problem in lint_metric(name, kind, ()):
            problems.append(f"{path}: {problem}")
    assert problems == [], "\n".join(problems)


def _sample_registry() -> MetricsRegistry:
    registry = MetricsRegistry()
    registry.counter(
        "master_frame_results_total", "results", labels=("result",)
    ).inc(3, result="ok")
    registry.gauge("master_worker_queue_depth", "depth", labels=("worker",)).set(
        2, worker="w-1"
    )
    histogram = registry.histogram(
        "master_unit_latency_seconds", "latency", buckets=(0.1, 1.0, 10.0)
    )
    for value in (0.05, 0.5, 0.7, 5.0, 50.0):
        histogram.observe(value)
    return registry


def test_render_prometheus_layout():
    text = render_prometheus(_sample_registry().snapshot())
    lines = text.splitlines()
    assert "# TYPE master_frame_results_total counter" in lines
    assert 'master_frame_results_total{result="ok"} 3' in lines
    assert "# TYPE master_worker_queue_depth gauge" in lines
    assert 'master_worker_queue_depth{worker="w-1"} 2' in lines
    # Cumulative buckets, the +Inf overflow, then sum/count.
    assert 'master_unit_latency_seconds_bucket{le="0.1"} 1' in lines
    assert 'master_unit_latency_seconds_bucket{le="1"} 3' in lines
    assert 'master_unit_latency_seconds_bucket{le="10"} 4' in lines
    assert 'master_unit_latency_seconds_bucket{le="+Inf"} 5' in lines
    assert "master_unit_latency_seconds_sum 56.25" in lines
    assert "master_unit_latency_seconds_count 5" in lines
    # The +Inf line comes after every finite bucket of its series.
    bucket_lines = [
        line for line in lines
        if line.startswith("master_unit_latency_seconds_bucket")
    ]
    assert bucket_lines[-1].startswith(
        'master_unit_latency_seconds_bucket{le="+Inf"}'
    )
    assert text.endswith("\n")


def test_label_value_escaping_round_trip():
    registry = MetricsRegistry()
    nasty = 'job "x", a\\b\nnewline,k=v'
    registry.gauge("sched_job_share", "share", labels=("job",)).set(
        0.5, job=nasty
    )
    text = render_prometheus(registry.snapshot())
    parsed = parse_prometheus(text)
    (labels, value), = parsed["sched_job_share"]
    assert labels == {"job": nasty}
    assert value == 0.5


def test_render_refuses_nonconforming_metric():
    registry = MetricsRegistry()
    registry.gauge("master_queue", "no unit suffix").set(1)
    with pytest.raises(ValueError, match="unit suffix"):
        render_prometheus(registry.snapshot())
    assert lint_snapshot(registry.snapshot())


def test_parse_rejects_malformed_line():
    with pytest.raises(ValueError, match="Malformed"):
        parse_prometheus("this is not an exposition line at all {")


# ---------------------------------------------------------------------------
# Dashboard


def test_histogram_quantiles_reconstruction():
    registry = MetricsRegistry()
    histogram = registry.histogram(
        "master_unit_latency_seconds", "latency", buckets=(0.1, 1.0, 10.0)
    )
    for _ in range(90):
        histogram.observe(0.05)
    for _ in range(10):
        histogram.observe(5.0)
    samples = parse_prometheus(render_prometheus(registry.snapshot()))
    quantiles = histogram_quantiles(
        samples, "master_unit_latency_seconds", (0.5, 0.99)
    )
    assert quantiles[0.5] <= 0.1  # inside the first bucket
    assert 1.0 < quantiles[0.99] <= 10.0  # inside the tail bucket
    assert (
        histogram_quantiles(samples, "no_such_histogram_seconds", (0.5,)) is None
    )


def test_render_dashboard_sections():
    samples = parse_prometheus(
        render_prometheus(_sample_registry().snapshot())
    )
    clusterz = {
        "cluster": {
            "frames_total": 8,
            "frames_finished": 3,
            "frames_pending": 2,
            "workers": {
                "w-1": {"queue_depth": 2, "is_dead": False, "frames_stolen": 1}
            },
        },
        "jobs": {
            "render-a": {
                "frames_total": 8,
                "frames_finished": 3,
                "state": "running",
                "share_achieved": 0.5,
                "share_target": 0.75,
                "assembly": {
                    "tiles_per_frame": 4,
                    "frames_assembled": 1,
                    "frames_partial": 1,
                },
            }
        },
        "speculation": {"launched": 2, "outcomes": {"won": 1, "lost": 1}},
        "slo": {
            "jobs": {
                "render-a": {
                    "attainment": 0.97,
                    "burn": {"short": 1.5, "long": 0.8},
                    "firing": ["unit_latency_p99"],
                }
            },
            "alerts": [
                {
                    "at": 1000.0,
                    "job_name": "render-a",
                    "kind": "unit_latency_p99",
                    "transition": "fire",
                }
            ],
        },
    }
    text = render_dashboard(samples, clusterz, now=1000.0)
    assert "units: 3/8 finished, 2 pending" in text
    assert "w-1" in text and "live" in text
    assert "render-a" in text and "0.50" in text and "0.75" in text
    assert "unit latency" in text and "p99" in text
    assert "speculation" in text and "won 1" in text
    assert "assembly" in text and "1 stitched" in text
    assert "0.970" in text and "unit_latency_p99" in text
    assert "FIRE" in text
    # A worker endpoint (no cluster view) still renders a frame.
    assert "telemetry" in render_dashboard(samples, {})


# ---------------------------------------------------------------------------
# Telemetry endpoints


def _fetch(port: int, path: str):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    )


def test_endpoints_serve_metrics_healthz_clusterz():
    registry = _sample_registry()

    async def scenario():
        server = TelemetryServer(
            registry,
            port=0,
            clusterz_fn=lambda: {"cluster": {"frames_total": 4}},
            healthz_fn=lambda: {"role": "master"},
        )
        await server.start()
        try:
            port = server.port
            response = await asyncio.to_thread(_fetch, port, "/metrics")
            assert response.status == 200
            assert response.headers["Content-Type"] == CONTENT_TYPE
            parsed = parse_prometheus(response.read().decode("utf-8"))
            assert "master_frame_results_total" in parsed
            assert "master_unit_latency_seconds_bucket" in parsed

            response = await asyncio.to_thread(_fetch, port, "/healthz")
            payload = json.loads(response.read())
            assert payload["ok"] is True and payload["role"] == "master"
            assert payload["uptime_seconds"] >= 0

            response = await asyncio.to_thread(_fetch, port, "/clusterz")
            assert json.loads(response.read()) == {
                "cluster": {"frames_total": 4}
            }

            with pytest.raises(urllib.error.HTTPError) as not_found:
                await asyncio.to_thread(_fetch, port, "/nope")
            assert not_found.value.code == 404

            request = urllib.request.Request(
                f"http://127.0.0.1:{port}/metrics", data=b"x", method="POST"
            )
            with pytest.raises(urllib.error.HTTPError) as bad_method:
                await asyncio.to_thread(
                    lambda: urllib.request.urlopen(request, timeout=10)
                )
            assert bad_method.value.code == 405
        finally:
            await server.stop()

    asyncio.run(asyncio.wait_for(scenario(), 30))


def test_handler_failure_returns_500_with_error_body():
    """A raising view fn (or a lint-refused metric) must answer with a
    self-diagnosing 500, not an opaque connection reset."""

    def broken_clusterz():
        raise RuntimeError("view exploded")

    registry = MetricsRegistry()
    registry.gauge("master_queue", "no unit suffix -> lint-refused").set(1)

    async def scenario():
        server = TelemetryServer(
            registry, port=0, clusterz_fn=broken_clusterz
        )
        await server.start()
        try:
            for path, needle in (
                ("/clusterz", "view exploded"),
                ("/metrics", "unit suffix"),
            ):
                with pytest.raises(urllib.error.HTTPError) as err:
                    await asyncio.to_thread(_fetch, server.port, path)
                assert err.value.code == 500
                assert needle in json.loads(err.value.read())["error"]
        finally:
            await server.stop()

    asyncio.run(asyncio.wait_for(scenario(), 30))


def test_worker_style_endpoint_has_no_clusterz():
    async def scenario():
        server = TelemetryServer(MetricsRegistry(), port=0)
        await server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as not_found:
                await asyncio.to_thread(_fetch, server.port, "/clusterz")
            assert not_found.value.code == 404
            response = await asyncio.to_thread(_fetch, server.port, "/healthz")
            assert json.loads(response.read())["ok"] is True
        finally:
            await server.stop()

    asyncio.run(asyncio.wait_for(scenario(), 30))


def _job(
    frames: int,
    workers: int = 2,
    slo: JobSlo | None = None,
    name: str = "telemetry-e2e",
) -> BlenderJob:
    return BlenderJob(
        job_name=name,
        job_description="telemetry plane e2e",
        project_file_path="%BASE%/p.blend",
        render_script_path="%BASE%/s.py",
        frame_range_from=1,
        frame_range_to=frames,
        wait_for_number_of_workers=workers,
        frame_distribution_strategy=DistributionStrategy.naive_fine(),
        output_directory_path="%BASE%/out",
        output_file_name_format="rendered-#####",
        output_file_format="PNG",
        slo=slo,
    )


def test_live_cluster_scrapeable_mid_job():
    """The acceptance criterion: while a 2-worker job is in flight, the
    master's /metrics returns valid (lint-clean — the exporter refuses
    anything else) Prometheus exposition and /clusterz mirrors the live
    cluster_view."""
    from tpu_render_cluster.harness.local import _run
    from tpu_render_cluster.master.cluster import ClusterManager
    from tpu_render_cluster.worker.backends.mock import MockBackend

    job = _job(frames=6, workers=2)
    backends = [
        MockBackend(load_seconds=0.0, save_seconds=0.0, render_seconds=0.3)
        for _ in range(2)
    ]
    scraped: dict = {}

    async def on_cluster_started(manager, workers, worker_tasks) -> None:
        async def scrape():
            while manager.telemetry.port == 0:
                await asyncio.sleep(0.01)
            port = manager.telemetry.port
            # Wait until the job is actually running (workers joined).
            while True:
                response = await asyncio.to_thread(_fetch, port, "/clusterz")
                view = json.loads(response.read())
                states = [j.get("state") for j in (view.get("jobs") or {}).values()]
                if "running" in states:
                    break
                await asyncio.sleep(0.02)
            scraped["clusterz"] = view
            response = await asyncio.to_thread(_fetch, port, "/metrics")
            scraped["content_type"] = response.headers["Content-Type"]
            scraped["metrics"] = response.read().decode("utf-8")
            response = await asyncio.to_thread(_fetch, port, "/healthz")
            scraped["healthz"] = json.loads(response.read())

        scraped["task"] = asyncio.create_task(scrape())

    async def scenario():
        result = await _run(
            job,
            backends,
            manager_factory=lambda job: ClusterManager(
                "127.0.0.1",
                0,
                job,
                metrics=MetricsRegistry(),
                telemetry_port=0,
            ),
            on_cluster_started=on_cluster_started,
        )
        await scraped.pop("task")
        return result

    _trace, _worker_traces, manager, _workers = asyncio.run(
        asyncio.wait_for(scenario(), 60)
    )
    assert manager.state.all_frames_finished()
    # Mid-job: the scrape observed the running job with work outstanding.
    cluster = scraped["clusterz"]["cluster"]
    assert cluster["frames_finished"] < cluster["frames_total"] == 6
    assert len(cluster["workers"]) == 2
    # Valid exposition with the master families present, served with the
    # text-exposition content type.
    assert scraped["content_type"] == CONTENT_TYPE
    parsed = parse_prometheus(scraped["metrics"])
    assert "master_job_units" in parsed
    assert scraped["healthz"]["ok"] is True
    assert scraped["healthz"]["role"] == "master"
    assert scraped["healthz"]["workers_connected"] == 2
    # The endpoint is torn down with the server.
    with pytest.raises((urllib.error.URLError, OSError)):
        _fetch(manager.telemetry.port, "/healthz")


# ---------------------------------------------------------------------------
# SLO engine: units


def test_job_slo_toml_declaration(tmp_path):
    job_path = tmp_path / "job.toml"
    job_path.write_text(
        """
job_name = "slo-job"
job_description = "d"
project_file_path = "%BASE%/p.blend"
render_script_path = "%BASE%/s.py"
frame_range_from = 1
frame_range_to = 4
wait_for_number_of_workers = 1
output_directory_path = "%BASE%/out"
output_file_name_format = "r-####"
output_file_format = "PNG"

[frame_distribution_strategy]
strategy_type = "naive-fine"

[slo]
unit_latency_p99_seconds = 0.5
deadline_seconds = 120
"""
    )
    job = BlenderJob.load_from_file(job_path)
    assert job.slo == JobSlo(
        unit_latency_p99_seconds=0.5, deadline_seconds=120.0
    )
    # Round-trips through the wire dict form.
    assert BlenderJob.from_dict(job.to_dict()).slo == job.slo
    # Jobs without the table keep slo=None and a reference-identical dict.
    assert _job(4).slo is None
    assert "slo" not in _job(4).to_dict()


def test_job_slo_validation():
    with pytest.raises(ValueError, match="positive number"):
        JobSlo(unit_latency_p99_seconds=-1.0)
    # TOML booleans are int subclasses: `deadline_seconds = true` must be
    # an error, not a 1-second deadline.
    with pytest.raises(ValueError, match="positive number"):
        JobSlo.from_dict({"deadline_seconds": True})
    with pytest.raises(ValueError, match="no objective"):
        JobSlo()
    with pytest.raises(ValueError, match="unknown slo key"):
        JobSlo.from_dict({"latency": 1.0})
    with pytest.raises(ValueError, match="Invalid job"):
        _job(4, slo={"unit_latency_p99_seconds": "fast"})  # type: ignore[arg-type]


def _tracker(**kwargs) -> SloTracker:
    defaults = dict(
        started_at=0.0, short_window=10.0, long_window=30.0, threshold=1.0
    )
    defaults.update(kwargs)
    return SloTracker(
        "job-a", JobSlo(unit_latency_p99_seconds=1.0), **defaults
    )


def test_burn_rate_math():
    tracker = _tracker()
    for _ in range(9):
        tracker.observe(0.5, now=5.0)
    tracker.observe(2.0, now=5.0)  # 1 of 10 violates
    # Violation fraction 0.1 over a 1% budget -> burn 10x.
    assert tracker._burn(5.0, 10.0) == pytest.approx(10.0)
    assert tracker.attainment() == pytest.approx(0.9)


def test_exactly_once_fire_and_clear_edges():
    tracker = _tracker()
    tracker.observe(2.0, now=1.0)
    alerts = tracker.evaluate(1.0)
    assert [a.transition for a in alerts] == ["fire"]
    assert alerts[0].kind == KIND_UNIT_LATENCY
    # Re-evaluating a persisting breach never re-fires.
    for now in (1.5, 2.0, 5.0):
        assert tracker.evaluate(now) == []
    tracker.observe(2.0, now=6.0)
    assert tracker.evaluate(6.0) == []  # still the same episode
    # The short window slides past every violation -> one clear.
    alerts = tracker.evaluate(17.0)
    assert [a.transition for a in alerts] == ["clear"]
    assert tracker.evaluate(18.0) == []
    # A NEW breach is a new episode: second fire.
    tracker.observe(3.0, now=20.0)
    assert [a.transition for a in tracker.evaluate(20.0)] == ["fire"]
    assert tracker.fires[KIND_UNIT_LATENCY] == 2
    assert tracker.clears[KIND_UNIT_LATENCY] == 1


def test_fire_requires_both_windows_burning():
    # A violation older than the short window but inside the long one
    # must NOT fire (the multi-window rule: transient blips don't page).
    tracker = _tracker()
    tracker.observe(2.0, now=1.0)
    for _ in range(50):
        tracker.observe(0.1, now=14.0)
    assert tracker.evaluate(14.0) == []  # short window is clean
    assert tracker.firing.get(KIND_UNIT_LATENCY, False) is False


def test_min_window_samples_suppresses_sparse_burn():
    """With TRC_SLO_MIN_WINDOW_SAMPLES raised, a lone violation in a
    sparse window cannot fire; it becomes eligible once the window holds
    enough observations (and by then may have slid out)."""
    tracker = _tracker(min_samples=4)
    tracker.observe(2.0, now=1.0)
    assert tracker.evaluate(1.0) == []  # 1 sample < 4: suppressed
    for t in (1.5, 2.0):
        tracker.observe(0.1, now=t)
        assert tracker.evaluate(t) == []  # still < 4 samples
    tracker.observe(0.1, now=2.5)
    # 4 samples, 1 violating -> burn 25x the budget in both windows: fire.
    assert [a.transition for a in tracker.evaluate(2.5)] == ["fire"]


def test_deadline_fires_once_and_never_clears():
    tracker = SloTracker(
        "job-a",
        JobSlo(deadline_seconds=10.0),
        started_at=0.0,
        short_window=10.0,
        long_window=30.0,
        threshold=1.0,
    )
    assert tracker.evaluate(5.0) == []
    alerts = tracker.evaluate(11.0)
    assert [a.kind for a in alerts] == [KIND_DEADLINE]
    assert [a.transition for a in alerts] == ["fire"]
    for now in (12.0, 100.0):
        assert tracker.evaluate(now) == []
    tracker.finish(120.0)
    assert tracker.evaluate(120.0) == []
    assert tracker.fires == {KIND_DEADLINE: 1}
    assert tracker.clears == {}


def test_slo_service_plumbing():
    """One violating observation through the service must land in all
    three sinks: the alerts log, slo_alerts_total, and a Perfetto
    instant on the 'alerts' track — plus the attainment/burn gauges."""
    registry = MetricsRegistry()
    tracer = Tracer(process_name="master")
    service = SloService(metrics=registry, span_tracer=tracer)
    job = _job(4, slo=JobSlo(unit_latency_p99_seconds=0.5))
    assert service.register_job(job) is not None
    assert service.tracked()
    state = SimpleNamespace(job=job)
    service.observe_unit_latency(state, 1, 2.0)  # violates
    assert len(service.alerts) == 1
    alert = service.alerts[0]
    assert alert.transition == "fire" and alert.kind == KIND_UNIT_LATENCY
    assert (
        registry.counter(
            "slo_alerts_total", labels=("job", "kind", "transition")
        ).value(job=job.job_name, kind=KIND_UNIT_LATENCY, transition="fire")
        == 1
    )
    assert registry.gauge("slo_attainment_ratio", labels=("job",)).value(
        job=job.job_name
    ) == pytest.approx(0.0)
    assert registry.gauge(
        "slo_objective_seconds", labels=("job", "objective")
    ).value(job=job.job_name, objective=KIND_UNIT_LATENCY) == pytest.approx(0.5)
    instants = [
        e
        for e in tracer.events()
        if e.get("ph") == "i" and e.get("cat") == "slo"
    ]
    assert len(instants) == 1
    assert instants[0]["args"]["transition"] == "fire"
    # The whole registry stays exportable (lint-clean) with SLO series in.
    render_prometheus(registry.snapshot())
    # view() mirrors the firing state for /clusterz.
    view = service.view()
    assert view["jobs"][job.job_name]["firing"] == [KIND_UNIT_LATENCY]
    assert view["alerts"][0]["transition"] == "fire"
    # Jobs without objectives are a no-op registration.
    assert service.register_job(_job(4, name="plain")) is None


def test_control_plane_alerts_op():
    """The scheduler control plane serves the SLO alert log + live view
    via {"op": "alerts"} (sched/control.handle_request)."""
    from tpu_render_cluster.sched.control import handle_request

    service = SloService()
    job = _job(4, slo=JobSlo(unit_latency_p99_seconds=0.1))
    service.register_job(job)
    service.observe_unit_latency(SimpleNamespace(job=job), 1, 1.0)
    manager = SimpleNamespace(slo=service)
    response = asyncio.run(handle_request(manager, {"op": "alerts"}))
    assert response["ok"] is True
    assert response["alerts"][0]["transition"] == "fire"
    assert response["slo"]["jobs"][job.job_name]["units_violating"] == 1


# ---------------------------------------------------------------------------
# SLO engine: e2e


def test_slo_breach_and_recovery_e2e(monkeypatch):
    """Deterministic breach-and-recovery through a REAL cluster run: one
    slow first frame violates the declared p99 objective (fire), then a
    long tail of fast frames slides it out of the short burn window
    (clear) — each edge exactly once, asserted on the master's own SLO
    state after the run."""
    monkeypatch.setenv("TRC_SLO_SHORT_WINDOW_SECONDS", "0.5")
    monkeypatch.setenv("TRC_SLO_LONG_WINDOW_SECONDS", "1.0")
    monkeypatch.setenv("TRC_SLO_TICK_SECONDS", "0.05")
    from tpu_render_cluster.harness.local import _run_local_job_full
    from tpu_render_cluster.worker.backends.mock import MockBackend

    frames = 31
    job = _job(
        frames,
        workers=1,
        slo=JobSlo(unit_latency_p99_seconds=0.25),
        name="slo-recovery",
    )
    backend = MockBackend(
        load_seconds=0.0,
        save_seconds=0.0,
        # Frame 1 violates the 0.25 s objective; the 30-frame fast tail
        # is >= 0.6 s of sleep lower bound, strictly longer than the
        # 0.5 s short window -> the breach must clear by job end.
        render_seconds_fn=lambda frame: 0.5 if frame == 1 else 0.02,
    )
    _trace, _worker_traces, manager, _workers = _run_local_job_full(
        job, [backend], 60.0
    )
    assert manager.state.all_frames_finished()
    tracker = manager.slo.trackers[job.job_name]
    assert tracker.fires == {KIND_UNIT_LATENCY: 1}
    assert tracker.clears == {KIND_UNIT_LATENCY: 1}
    assert tracker.firing[KIND_UNIT_LATENCY] is False
    assert tracker.units_observed == frames
    assert tracker.units_violating == 1
    assert tracker.attainment() == pytest.approx(1.0 - 1.0 / frames)
    transitions = [a.transition for a in manager.slo.alerts]
    assert transitions == ["fire", "clear"]
    # The counter ledger matches the exactly-once edges.
    counter = manager.metrics.counter(
        "slo_alerts_total", labels=("job", "kind", "transition")
    )
    assert counter.value(
        job=job.job_name, kind=KIND_UNIT_LATENCY, transition="fire"
    ) == 1
    assert counter.value(
        job=job.job_name, kind=KIND_UNIT_LATENCY, transition="clear"
    ) == 1
    # The alert instants landed on the Perfetto "alerts" track.
    slo_instants = [
        e
        for e in manager.span_tracer.events()
        if e.get("ph") == "i" and e.get("cat") == "slo"
    ]
    assert len(slo_instants) == 2
    # And cluster_view carries the slo section for /clusterz consumers.
    assert manager.cluster_view()["slo"]["jobs"][job.job_name]["finished"]


@pytest.mark.chaos
def test_seeded_chaos_slo_breach(monkeypatch):
    """Satellite acceptance: a seeded straggler plan drives a declared
    p99 objective into burn — the alert fires EXACTLY once for the whole
    breach episode (one episode: the straggler never recovers, so no
    clear), the chaos invariant audit stays green, and the report's slo
    section carries the verdict."""
    from tpu_render_cluster.chaos.plan import FaultPlan
    from tpu_render_cluster.chaos.runner import run_chaos_job

    monkeypatch.delenv("TRC_SLO_SHORT_WINDOW_SECONDS", raising=False)
    monkeypatch.delenv("TRC_SLO_LONG_WINDOW_SECONDS", raising=False)
    plan = FaultPlan.generate(
        907,
        3,
        kills=0,
        partitions=0,
        duplicate_sends=0,
        stragglers=1,
        wedges=0,
        drops=0,
        dispatch_delays=0,
    )
    report = run_chaos_job(
        plan,
        frames=18,
        timeout=120.0,
        # The straggler stretches renders 3-5x; everything it touches
        # blows the objective while healthy units stay inside it.
        slo=JobSlo(unit_latency_p99_seconds=0.3),
    )
    assert report.ok, report.violations
    slo = report.stats["slo"]
    tracker_view = slo["jobs"][f"chaos-seed-{plan.seed}"]
    assert tracker_view["fires"] == {KIND_UNIT_LATENCY: 1}
    assert tracker_view["clears"] == {}
    assert tracker_view["units_observed"] == 18
    assert tracker_view["units_violating"] >= 1
    fire_edges = [a for a in slo["alerts"] if a["transition"] == "fire"]
    assert len(fire_edges) == 1


# ---------------------------------------------------------------------------
# statistics.json folds


def test_summarize_slo_section():
    from tpu_render_cluster.analysis.obs_events import summarize_slo

    assert summarize_slo([{}]) is None
    snapshots = [
        {
            "written_at": 5.0,
            "metrics": {
                "slo_alerts_total": {
                    "series": {
                        "job=a,kind=unit_latency_p99,transition=fire": 1.0
                    }
                }
            },
            "slo": {
                "jobs": {"a": {"attainment": 0.9, "firing": []}},
                "alerts": [{"job_name": "a", "transition": "fire"}],
            },
        },
        {  # older snapshot must not win the live view
            "written_at": 1.0,
            "metrics": {},
            "slo": {"jobs": {"a": {"attainment": 0.5}}},
        },
    ]
    section = summarize_slo(snapshots)
    assert section["jobs"]["a"]["attainment"] == 0.9
    assert section["alerts"][0]["transition"] == "fire"
    assert section["alerts_total"] == {
        "job=a,kind=unit_latency_p99,transition=fire": 1.0
    }


def test_summarize_obs_includes_slo():
    from tpu_render_cluster.analysis.obs_events import summarize_obs

    out = summarize_obs(
        [],
        [
            {
                "written_at": 2.0,
                "metrics": {},
                "slo": {"jobs": {"a": {"attainment": 1.0}}},
            }
        ],
    )
    assert out["slo"]["jobs"]["a"]["attainment"] == 1.0


# ---------------------------------------------------------------------------
# CLI failure-path artifact export (satellite)


def test_run_job_cli_exports_artifacts_on_failure(tmp_path, monkeypatch):
    """A raising job must still leave the obs artifacts behind: span
    timeline, merged cluster trace, metrics snapshot (with the final
    ledger), and the cost-model snapshot — the PR-7 assembly
    drain-on-failure pattern applied to the master CLI."""
    from tpu_render_cluster.master.cluster import ClusterManager
    from tpu_render_cluster.master.main import build_parser, run_job_command

    job_path = tmp_path / "job.toml"
    job_path.write_text(
        """
job_name = "doomed"
job_description = "d"
project_file_path = "%BASE%/p.blend"
render_script_path = "%BASE%/s.py"
frame_range_from = 1
frame_range_to = 2
wait_for_number_of_workers = 1
output_directory_path = "%BASE%/out"
output_file_name_format = "r-####"
output_file_format = "PNG"

[frame_distribution_strategy]
strategy_type = "naive-fine"
"""
    )

    async def doomed(self):
        raise RuntimeError("worker pool collapsed")

    monkeypatch.setattr(
        ClusterManager, "initialize_server_and_run_job", doomed
    )
    results = tmp_path / "results"
    args = build_parser().parse_args(
        [
            "run-job",
            str(job_path),
            "--resultsDirectory",
            str(results),
        ]
    )
    with pytest.raises(RuntimeError, match="worker pool collapsed"):
        asyncio.run(run_job_command(args))
    assert list(results.glob("*_job-doomed_trace-events.json"))
    assert list(results.glob("*_job-doomed_cluster_trace-events.json"))
    metrics_files = list(results.glob("*_job-doomed_metrics.json"))
    assert metrics_files
    snapshot = json.loads(metrics_files[0].read_text())
    assert "metrics" in snapshot and "cluster" in snapshot
    # The success-only artifacts are correctly absent.
    assert not list(results.glob("*_raw-trace.json"))
