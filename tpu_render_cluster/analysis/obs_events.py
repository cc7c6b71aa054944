"""Loaders for the obs subsystem's artifacts (reference has no analog).

Three file families land next to the legacy ``*_raw-trace.json``:

- ``*_trace-events.json`` — Chrome trace-event JSON (Perfetto-loadable)
  with master / worker / transport spans, one file per process clock;
- ``*_cluster_trace-events.json`` — the MERGED cluster timeline: every
  process's spans rebased onto the master clock by the heartbeat
  clock-offset estimates, with flow arrows per frame lifecycle
  (obs/timeline.py);
- ``*_metrics.json`` — metrics registry snapshots (+ the cluster view and
  per-worker heartbeat payload aggregation).

This module validates and loads all of them so ``run_all`` can fold
live-signal summaries (per-phase span statistics, span counts by
category, and the cluster timelines' critical-path/straggler analysis)
into ``statistics.json`` alongside the legacy post-hoc metrics.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

TRACE_EVENTS_GLOB = "*_trace-events.json"
METRICS_SNAPSHOT_GLOB = "*_metrics.json"
# Merged, clock-corrected cluster timelines (obs/timeline.py). They match
# TRACE_EVENTS_GLOB too, so the per-process finder excludes them — their
# events are the per-process files' events re-based, and counting both
# would double every span in the roll-up. The leading underscore is part
# of the discriminator: exporters write "<prefix>_cluster_trace-events.json",
# and a run PREFIX that merely ends in "cluster" must not be misclassified.
CLUSTER_TRACE_SUFFIX = "_cluster_trace-events.json"


def find_trace_event_files(results_directory: str | Path) -> list[Path]:
    return sorted(
        path
        for path in Path(results_directory).rglob(TRACE_EVENTS_GLOB)
        if not path.name.endswith(CLUSTER_TRACE_SUFFIX)
    )


def find_cluster_trace_files(results_directory: str | Path) -> list[Path]:
    return sorted(Path(results_directory).rglob(f"*{CLUSTER_TRACE_SUFFIX}"))


def find_metrics_files(results_directory: str | Path) -> list[Path]:
    return sorted(Path(results_directory).rglob(METRICS_SNAPSHOT_GLOB))


# Flight-recorder post-mortem bundles (obs/flightrec.py).
BLACKBOX_GLOB = "*_blackbox.json"


def find_blackbox_files(results_directory: str | Path) -> list[Path]:
    return sorted(Path(results_directory).rglob(BLACKBOX_GLOB))


@dataclass(frozen=True)
class ObsTrace:
    """One loaded trace-event file."""

    path: Path
    events: list[dict[str, Any]]

    def spans(self) -> list[dict[str, Any]]:
        """Complete ('X') events only — the duration-carrying spans."""
        return [e for e in self.events if e.get("ph") == "X"]

    def span_seconds_by_name(self) -> dict[str, list[float]]:
        out: dict[str, list[float]] = {}
        for event in self.spans():
            out.setdefault(str(event.get("name")), []).append(
                float(event.get("dur", 0.0)) / 1e6
            )
        return out

    def span_count_by_category(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for event in self.spans():
            cat = str(event.get("cat", "default"))
            out[cat] = out.get(cat, 0) + 1
        return out


def load_trace_events(path: str | Path) -> ObsTrace:
    """Load + validate one Chrome trace-event file.

    Accepts both container formats the viewers accept: the JSON Object
    Format (``{"traceEvents": [...]}`` — what this repo writes) and the
    bare JSON Array Format.
    """
    path = Path(path)
    data = json.loads(path.read_text(encoding="utf-8"))
    if isinstance(data, dict):
        events = data.get("traceEvents")
    elif isinstance(data, list):
        events = data
    else:
        raise ValueError(f"{path}: not a Chrome trace-event document")
    if not isinstance(events, list):
        raise ValueError(f"{path}: traceEvents must be a list")
    for event in events:
        if not isinstance(event, dict) or "ph" not in event:
            raise ValueError(f"{path}: malformed trace event: {event!r}")
        if event["ph"] == "X" and ("ts" not in event or "dur" not in event):
            raise ValueError(f"{path}: complete event missing ts/dur: {event!r}")
    return ObsTrace(path=path, events=events)


def load_metrics_snapshot(path: str | Path) -> dict[str, Any]:
    """Load + validate one metrics snapshot file."""
    path = Path(path)
    data = json.loads(path.read_text(encoding="utf-8"))
    if not isinstance(data, dict) or "metrics" not in data:
        raise ValueError(f"{path}: not a metrics snapshot (missing 'metrics')")
    if not isinstance(data["metrics"], dict):
        raise ValueError(f"{path}: 'metrics' must be an object")
    return data


def load_obs_artifacts(
    results_directory: str | Path,
    *,
    on_error: "Callable[[Path, Exception], None] | None" = None,
) -> tuple[list[ObsTrace], list[dict[str, Any]]]:
    """Load every obs artifact under a results directory (both families).

    With ``on_error`` set, a malformed file is reported to it and skipped
    so one bad artifact doesn't discard the rest of the population;
    without it, the first malformed file raises.
    """
    traces: list[ObsTrace] = []
    metrics: list[dict[str, Any]] = []
    for loader, sink, paths in (
        (load_trace_events, traces, find_trace_event_files(results_directory)),
        (load_metrics_snapshot, metrics, find_metrics_files(results_directory)),
    ):
        for path in paths:
            try:
                sink.append(loader(path))
            except (ValueError, OSError, json.JSONDecodeError) as e:
                if on_error is None:
                    raise
                on_error(path, e)
    return traces, metrics


def load_cluster_traces(
    results_directory: str | Path,
    *,
    on_error: "Callable[[Path, Exception], None] | None" = None,
) -> list[ObsTrace]:
    """Load every merged cluster timeline under a results directory."""
    traces: list[ObsTrace] = []
    for path in find_cluster_trace_files(results_directory):
        try:
            traces.append(load_trace_events(path))
        except (ValueError, OSError, json.JSONDecodeError) as e:
            if on_error is None:
                raise
            on_error(path, e)
    return traces


def load_blackbox_bundles(
    results_directory: str | Path,
    *,
    on_error: "Callable[[Path, Exception], None] | None" = None,
) -> list[dict[str, Any]]:
    """Load every flight-recorder bundle under a results directory; each
    returned dict gains a ``path`` key for provenance."""
    bundles: list[dict[str, Any]] = []
    for path in find_blackbox_files(results_directory):
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
            if not isinstance(data, dict) or not isinstance(
                data.get("blackbox"), dict
            ):
                raise ValueError("not a flight-recorder bundle")
            bundles.append({**data, "path": str(path)})
        except (ValueError, OSError, json.JSONDecodeError) as e:
            if on_error is None:
                raise
            on_error(path, e)
    return bundles


def _percentile(sorted_values: list[float], q: float) -> float:
    if not sorted_values:
        return 0.0
    index = min(len(sorted_values) - 1, int(round(q * (len(sorted_values) - 1))))
    return sorted_values[index]


def _consume_metric_snapshots(
    metrics: list[dict[str, Any]], take_registry, take_wire
) -> None:
    """Walk metric snapshots, consuming every series exactly once.

    ``take_registry(names) -> bool`` is fed registry-snapshot forms (a
    snapshot's own ``metrics``, the harness's per-worker ``workers``,
    and process_metrics — see below), returning whether it consumed
    anything; ``take_wire(wire)`` gets the compact heartbeat wire form
    (``cluster_metrics``), consumed only when no registry snapshot
    covered that file, so nothing is double-counted.

    The harness's process-global snapshots are CUMULATIVE per process
    (every job a harness process runs re-exports the same counters):
    only the NEWEST snapshot per pid is consumed, once — summing every
    file's copy would multiply counters by the job count and re-weight
    histogram means toward earlier jobs.
    """
    newest_per_pid: dict[Any, tuple[float, dict[str, Any]]] = {}
    snapshots_with_process_metrics: set[int] = set()
    for snapshot_index, snapshot in enumerate(metrics):
        process_entry = snapshot.get("process_metrics")
        if isinstance(process_entry, dict) and isinstance(
            process_entry.get("metrics"), dict
        ):
            snapshots_with_process_metrics.add(snapshot_index)
            pid = process_entry.get("pid")
            written_at = float(snapshot.get("written_at", 0.0))
            best = newest_per_pid.get(pid)
            if best is None or written_at >= best[0]:
                newest_per_pid[pid] = (written_at, process_entry["metrics"])

    for snapshot_index, snapshot in enumerate(metrics):
        took_registries = snapshot_index in snapshots_with_process_metrics
        take_registry(snapshot.get("metrics", {}))
        for worker_registry in (snapshot.get("workers") or {}).values():
            if isinstance(worker_registry, dict) and take_registry(worker_registry):
                took_registries = True
        if not took_registries:
            wire = snapshot.get("cluster_metrics")
            if isinstance(wire, dict):
                take_wire(wire)
    for _written_at, registry in newest_per_pid.values():
        take_registry(registry)


def summarize_launch_occupancy(metrics: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Roll up the bounce-launch series of deep mesh frames (fed by
    worker/backends/tpu_raytrace.py): ``render_launch_occupancy`` (per
    launch, live rays / the width the program ran it at) and the lane
    counters ``render_pool_launched_lanes_total`` /
    ``render_pool_live_lanes_total``.

    Both shapes the snapshot families carry: registry-snapshot form (the
    snapshot's own ``metrics``, the harness's per-worker ``workers``, the
    newest ``process_metrics`` per pid) and the compact heartbeat wire
    form (the master CLI's merged ``cluster_metrics``, consumed only when
    no registry snapshot covered that file, so nothing is counted twice).
    None when no deep frame was rendered.
    """
    launches = 0
    occupancy_sum = 0.0
    lanes = {
        "render_pool_launched_lanes_total": 0.0,
        "render_pool_live_lanes_total": 0.0,
    }

    def take_registry(names: dict[str, Any]) -> bool:
        nonlocal launches, occupancy_sum
        took = False
        histogram = names.get("render_launch_occupancy")
        if histogram:
            took = True
            for series in histogram.get("series", {}).values():
                launches += int(series.get("count", 0))
                occupancy_sum += float(series.get("sum", 0.0))
        for name in lanes:
            counter = names.get(name)
            if counter:
                took = True
                lanes[name] += sum(
                    float(v) for v in counter.get("series", {}).values()
                )
        return took

    def take_wire(wire: dict[str, Any]) -> None:
        nonlocal launches, occupancy_sum
        for key, entry in (wire.get("h") or {}).items():
            if key.partition("|")[0] == "render_launch_occupancy":
                launches += int(entry.get("n", 0))
                occupancy_sum += float(entry.get("s", 0.0))
        for key, value in (wire.get("c") or {}).items():
            name = key.partition("|")[0]
            if name in lanes:
                lanes[name] += float(value)

    _consume_metric_snapshots(metrics, take_registry, take_wire)
    launched = lanes["render_pool_launched_lanes_total"]
    if not launches or launched <= 0:
        return None
    live = lanes["render_pool_live_lanes_total"]
    return {
        "launches": launches,
        # Per launch: every bounce weighs the same, however narrow.
        "launch_occupancy_mean": occupancy_sum / launches,
        "launched_lanes_total": launched,
        "live_lanes_total": live,
        # Lane-weighted: what share of the launched lanes carried a ray.
        "live_lane_share": live / launched,
    }


def summarize_sched(metrics: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Roll the multi-job scheduler's evidence up (sched/ artifacts).

    Metrics snapshots written by a ``sched.JobManager`` carry a top-level
    ``sched`` section (``scheduler_view()``) whose per-job views hold the
    lifecycle record: makespan, admission wait, achieved vs. target share
    over the multi-job overlap window, preemption counts, and the per-job
    exactly-once ledger. Jobs are keyed ``<job_name>:<job_id>``; when the
    same key appears in several snapshots (the live 1 Hz file plus the
    final one) the newest ``written_at`` wins. None when no snapshot came
    from a scheduler run — single-job runs get no ``sched`` section.
    """
    jobs: dict[str, tuple[float, dict[str, Any]]] = {}
    for snapshot in metrics:
        sched = snapshot.get("sched")
        if not isinstance(sched, dict):
            continue
        written_at = float(snapshot.get("written_at", 0.0))
        for job_id, view in (sched.get("jobs") or {}).items():
            if not isinstance(view, dict):
                continue
            key = f"{view.get('job_name', '?')}:{job_id}"
            share = view.get("share") if isinstance(view.get("share"), dict) else {}
            entry = {
                "job_id": job_id,
                "job_name": view.get("job_name"),
                "status": view.get("status"),
                "weight": view.get("weight"),
                "priority": view.get("priority"),
                "frames_total": view.get("frames_total"),
                "admission_wait_seconds": view.get("admission_wait_seconds"),
                "makespan_seconds": view.get("makespan_seconds"),
                "preemptions": view.get("preemptions", 0),
                "share_target": share.get("target"),
                "share_achieved": share.get("achieved"),
                "overlap_seconds": share.get("overlap_seconds"),
                "ledger": view.get("ledger"),
            }
            best = jobs.get(key)
            if best is None or written_at >= best[0]:
                jobs[key] = (written_at, entry)
    if not jobs:
        return None
    entries = {key: entry for key, (_at, entry) in sorted(jobs.items())}
    makespans = [
        e["makespan_seconds"]
        for e in entries.values()
        if isinstance(e.get("makespan_seconds"), (int, float))
    ]
    out: dict[str, Any] = {
        "jobs": entries,
        "jobs_total": len(entries),
        "finished": sum(1 for e in entries.values() if e["status"] == "finished"),
        "cancelled": sum(1 for e in entries.values() if e["status"] == "cancelled"),
        "preemptions_total": sum(
            int(e.get("preemptions") or 0) for e in entries.values()
        ),
    }
    if makespans:
        out["makespan_seconds_max"] = max(makespans)
        out["makespan_seconds_mean"] = sum(makespans) / len(makespans)
    return out


def summarize_tiles(metrics: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Roll the master's tile-assembly evidence up (tiled jobs, PR 7).

    Aggregates ``master_frames_assembled_total`` (by stitch outcome) and
    the ``master_frame_assembly_seconds`` histogram from the master's
    registry snapshots, plus each job view's ``assembly`` section when
    present. None when no snapshot shows an assembled frame — untiled
    runs get no ``tiles`` section. The per-tile straggler scores and
    assembly-wait attribution live under ``critical_path.*.tiles``
    (analysis/critical_path.tile_statistics), derived from the merged
    cluster timeline's per-unit lifecycles.
    """
    assembled: dict[str, float] = {}
    stitch_count = 0
    stitch_sum = 0.0
    jobs: dict[str, Any] = {}
    for snapshot in metrics:
        names = snapshot.get("metrics", {})
        counter = names.get("master_frames_assembled_total")
        if counter:
            for label, value in counter.get("series", {}).items():
                key = label.partition("=")[2] or label or "total"
                assembled[key] = assembled.get(key, 0.0) + float(value)
        histogram = names.get("master_frame_assembly_seconds")
        if histogram:
            for series in histogram.get("series", {}).values():
                stitch_count += int(series.get("count", 0))
                stitch_sum += float(series.get("sum", 0.0))
        for job_name, view in (snapshot.get("jobs") or {}).items():
            if isinstance(view, dict) and isinstance(view.get("assembly"), dict):
                jobs[job_name] = view["assembly"]
    if not assembled:
        return None
    out: dict[str, Any] = {
        "frames_assembled": assembled,
        "stitch_count": stitch_count,
        "stitch_seconds_total": stitch_sum,
    }
    if stitch_count:
        out["stitch_seconds_mean"] = stitch_sum / stitch_count
    if jobs:
        out["jobs"] = jobs
    return out


def summarize_prediction(metrics: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Roll the predictive-scheduling evidence up (sched/cost_model.py +
    master/speculate.py).

    Three families: the cost model's prediction quality
    (``sched_cost_model_abs_error_seconds`` — absolute error of each
    per-unit prediction at observation time, i.e. predicted vs actual),
    the per-unit winning-result latency distribution
    (``master_unit_latency_seconds`` — what speculation is judged on),
    and the speculation ledger (``sched_speculations_total{outcome}`` +
    the launched counter). The live ``prediction``/``speculation``
    sections a master's cluster_view stamps into its snapshots ride
    along (newest snapshot wins). None when no snapshot carries any of
    it — runs without the predictive layer get no ``prediction`` section.
    """
    found = False
    abs_error_count = 0
    abs_error_sum = 0.0
    latency_count = 0
    latency_sum = 0.0
    speculations: dict[str, float] = {}
    launched = 0.0
    live: dict[str, Any] = {}
    # Newest-wins PER SECTION: snapshots from different masters may each
    # carry only one of the two live views, and one must not age out the
    # other.
    live_at: dict[str, float] = {}

    def take_registry(names: dict[str, Any]) -> bool:
        nonlocal found, abs_error_count, abs_error_sum
        nonlocal latency_count, latency_sum, launched
        took = False
        histogram = names.get("sched_cost_model_abs_error_seconds")
        if histogram:
            found = took = True
            for series in histogram.get("series", {}).values():
                abs_error_count += int(series.get("count", 0))
                abs_error_sum += float(series.get("sum", 0.0))
        histogram = names.get("master_unit_latency_seconds")
        if histogram:
            found = took = True
            for series in histogram.get("series", {}).values():
                latency_count += int(series.get("count", 0))
                latency_sum += float(series.get("sum", 0.0))
        counter = names.get("sched_speculations_total")
        if counter:
            found = took = True
            for label, value in counter.get("series", {}).items():
                outcome = label.partition("=")[2] or label or "total"
                speculations[outcome] = speculations.get(outcome, 0.0) + float(
                    value
                )
        counter = names.get("sched_speculations_launched_total")
        if counter:
            found = took = True
            launched += sum(
                float(v) for v in counter.get("series", {}).values()
            )
        return took

    def take_wire(wire: dict[str, Any]) -> None:
        nonlocal found, abs_error_count, abs_error_sum
        nonlocal latency_count, latency_sum, launched
        for key, entry in (wire.get("h") or {}).items():
            name = key.partition("|")[0]
            if name == "sched_cost_model_abs_error_seconds":
                found = True
                abs_error_count += int(entry.get("n", 0))
                abs_error_sum += float(entry.get("s", 0.0))
            elif name == "master_unit_latency_seconds":
                found = True
                latency_count += int(entry.get("n", 0))
                latency_sum += float(entry.get("s", 0.0))
        for key, value in (wire.get("c") or {}).items():
            name, _, label = key.partition("|")
            if name == "sched_speculations_total":
                found = True
                outcome = label.partition("=")[2] or label or "total"
                speculations[outcome] = speculations.get(outcome, 0.0) + float(
                    value
                )
            elif name == "sched_speculations_launched_total":
                found = True
                launched += float(value)

    _consume_metric_snapshots(metrics, take_registry, take_wire)
    for snapshot in metrics:
        written_at = float(snapshot.get("written_at", 0.0))
        for section in ("prediction", "speculation"):
            view = snapshot.get(section)
            if isinstance(view, dict) and written_at >= live_at.get(section, -1.0):
                live[section] = view
                live_at[section] = written_at
                found = True
    if not found:
        return None
    out: dict[str, Any] = {}
    if abs_error_count:
        out["abs_error"] = {
            "count": abs_error_count,
            "mean_s": abs_error_sum / abs_error_count,
        }
    if latency_count:
        out["unit_latency"] = {
            "count": latency_count,
            "mean_s": latency_sum / latency_count,
        }
    if speculations or launched:
        out["speculations"] = {
            "launched": launched,
            "outcomes": speculations,
        }
    out.update(live)
    return out


def summarize_slo(metrics: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Roll the SLO engine's evidence up (obs/slo.py).

    Two sources: the live ``slo`` section a master's cluster_view stamps
    into its snapshots (per-job attainment, burn windows, firing set, and
    the bounded alert log — newest snapshot wins, it is cumulative), and
    the ``slo_alerts_total`` registry counter (fire/clear edges per job
    and kind, summed across snapshot families). None when no snapshot
    carries either — jobs without an ``[slo]`` table get no section.
    """
    alerts_total: dict[str, float] = {}
    live: dict[str, Any] | None = None
    live_at = -1.0

    def take_registry(names: dict[str, Any]) -> bool:
        counter = names.get("slo_alerts_total")
        if not counter:
            return False
        for label, value in counter.get("series", {}).items():
            alerts_total[label or "total"] = alerts_total.get(
                label or "total", 0.0
            ) + float(value)
        return True

    def take_wire(wire: dict[str, Any]) -> None:
        for key, value in (wire.get("c") or {}).items():
            name, _, label = key.partition("|")
            if name == "slo_alerts_total":
                alerts_total[label or "total"] = alerts_total.get(
                    label or "total", 0.0
                ) + float(value)

    _consume_metric_snapshots(metrics, take_registry, take_wire)
    for snapshot in metrics:
        written_at = float(snapshot.get("written_at", 0.0))
        view = snapshot.get("slo")
        if isinstance(view, dict) and view and written_at >= live_at:
            live = view
            live_at = written_at
    if live is None and not alerts_total:
        return None
    out: dict[str, Any] = {}
    if live is not None:
        if isinstance(live.get("jobs"), dict):
            out["jobs"] = live["jobs"]
        if live.get("alerts"):
            out["alerts"] = live["alerts"]
    if alerts_total:
        out["alerts_total"] = alerts_total
    return out


def summarize_history(
    metrics: list[dict[str, Any]],
    flight_bundles: list[dict[str, Any]] | None = None,
) -> dict[str, Any] | None:
    """Roll the continuous-observability evidence up (obs/history.py +
    obs/flightrec.py).

    The ``history`` section a master stamps into its metrics snapshots
    carries per-counter increase/rate/trend and per-gauge envelopes over
    the run's sampled window — newest snapshot wins (it is cumulative
    over the retained ring). Flight-recorder bundles contribute a
    post-mortem ledger: dumps per trigger and each bundle's covered
    window. None when no snapshot carries a history section and no
    bundles exist — uninstrumented populations get no section.
    """
    live: dict[str, Any] | None = None
    live_at = -1.0
    for snapshot in metrics:
        written_at = float(snapshot.get("written_at", 0.0))
        section = snapshot.get("history")
        if isinstance(section, dict) and section and written_at >= live_at:
            live = section
            live_at = written_at
    out: dict[str, Any] = {}
    if live is not None:
        for key in (
            "interval_seconds",
            "retention_seconds",
            "samples",
            "resets_total",
            "window",
        ):
            if key in live:
                out[key] = live[key]
        # Rate trends: keep only series that actually moved — the roll-up
        # reads as "what was happening", not a registry dump.
        counters = {
            key: entry
            for key, entry in (live.get("counters") or {}).items()
            if isinstance(entry, dict) and entry.get("increase")
        }
        if counters:
            out["counters"] = counters
        if live.get("gauges"):
            out["gauges"] = live["gauges"]
    if flight_bundles:
        triggers: dict[str, int] = {}
        windows: list[dict[str, Any]] = []
        for bundle in flight_bundles:
            box = bundle.get("blackbox") or {}
            trigger = str(box.get("trigger", "unknown"))
            triggers[trigger] = triggers.get(trigger, 0) + 1
            windows.append(
                {
                    "trigger": trigger,
                    "window": box.get("window"),
                    "dumped_at": box.get("dumped_at"),
                    "path": bundle.get("path"),
                }
            )
        out["flight_bundles"] = {
            "count": len(flight_bundles),
            "triggers": triggers,
            "bundles": windows,
        }
    return out or None


def _parse_series_labels(label: str) -> dict[str, str]:
    """Registry series key (``"tag=ping,direction=send"``) -> labels."""
    labels: dict[str, str] = {}
    for part in label.split(","):
        name, sep, value = part.partition("=")
        if sep:
            labels[name] = value
    return labels


def summarize_attribution(
    metrics: list[dict[str, Any]],
    critical_sections: dict[str, Any] | None = None,
    *,
    worker_seconds: float | None = None,
) -> dict[str, Any] | None:
    """Roll the whole-stack time-attribution inputs up and partition them.

    Extracts the tick-phase histogram (``sched_tick_seconds``,
    sched/tickprof.py), the loop-lag families (``obs_loop_lag_seconds``
    + ``obs_loop_blocked_episodes_total``, obs/loopmon.py), the wire
    accounting families (``transport_serialize_seconds`` +
    ``transport_message_bytes_total``, transport/wirecost.py), and the
    device seconds (the sum of ``worker_frame_step_seconds{step=
    "device_wait"}``: the time a frame's host thread was blocked on the
    device, worker/queue.py), then hands them to
    ``analysis/attribution.attribution_report`` against the per-worker
    busy/idle windows in ``critical_sections`` (or the explicit
    ``worker_seconds`` denominator). Same snapshot-family handling as
    every other summarize_*: registry forms first, the compact wire form
    only for files no registry snapshot covered. None when no snapshot
    carries any attribution series.
    """
    found = False
    tick_phases: dict[str, dict[str, float]] = {}
    lag_roles: dict[str, dict[str, float]] = {}
    episode_roles: dict[str, float] = {}
    talker_rows: dict[str, dict[str, float]] = {}
    transport_s = 0.0
    device_s = 0.0

    def take_tick(phase: str, count: float, total: float) -> None:
        nonlocal found
        found = True
        entry = tick_phases.setdefault(phase, {"count": 0.0, "sum_s": 0.0})
        entry["count"] += count
        entry["sum_s"] += total

    def take_lag(role: str, count: float, total: float, peak: float) -> None:
        nonlocal found
        found = True
        entry = lag_roles.setdefault(
            role, {"samples": 0.0, "sum_s": 0.0, "max_s": 0.0}
        )
        entry["samples"] += count
        entry["sum_s"] += total
        entry["max_s"] = max(entry["max_s"], peak)

    def take_wire_bytes(labels: dict[str, str], value: float) -> None:
        nonlocal found
        found = True
        tag = labels.get("tag", "?")
        row = talker_rows.setdefault(
            tag, {"bytes": 0.0, "send_bytes": 0.0, "recv_bytes": 0.0,
                  "serialize_s": 0.0}
        )
        row["bytes"] += value
        direction = labels.get("direction")
        if direction == "send":
            row["send_bytes"] += value
        elif direction == "recv":
            row["recv_bytes"] += value

    def take_serialize(labels: dict[str, str], total: float) -> None:
        nonlocal found, transport_s
        found = True
        transport_s += total
        tag = labels.get("tag", "?")
        row = talker_rows.setdefault(
            tag, {"bytes": 0.0, "send_bytes": 0.0, "recv_bytes": 0.0,
                  "serialize_s": 0.0}
        )
        row["serialize_s"] += total

    def take_registry(names: dict[str, Any]) -> bool:
        nonlocal found, device_s
        took = False
        histogram = names.get("sched_tick_seconds")
        if histogram:
            took = True
            for label, series in histogram.get("series", {}).items():
                take_tick(
                    label.partition("=")[2] or label,
                    float(series.get("count", 0)),
                    float(series.get("sum", 0.0)),
                )
        histogram = names.get("obs_loop_lag_seconds")
        if histogram:
            took = True
            for label, series in histogram.get("series", {}).items():
                take_lag(
                    label.partition("=")[2] or label,
                    float(series.get("count", 0)),
                    float(series.get("sum", 0.0)),
                    float(series.get("max", 0.0) or 0.0),
                )
        counter = names.get("obs_loop_blocked_episodes_total")
        if counter:
            found = took = True
            for label, value in counter.get("series", {}).items():
                role = label.partition("=")[2] or label
                episode_roles[role] = episode_roles.get(role, 0.0) + float(value)
        counter = names.get("transport_message_bytes_total")
        if counter:
            took = True
            for label, value in counter.get("series", {}).items():
                take_wire_bytes(_parse_series_labels(label), float(value))
        histogram = names.get("transport_serialize_seconds")
        if histogram:
            took = True
            for label, series in histogram.get("series", {}).items():
                take_serialize(
                    _parse_series_labels(label), float(series.get("sum", 0.0))
                )
        histogram = names.get("worker_frame_step_seconds")
        if histogram:
            took = True
            series = histogram.get("series", {}).get("step=device_wait", {})
            device_s += float(series.get("sum", 0.0))
        return took

    def take_wire(wire: dict[str, Any]) -> None:
        nonlocal found, device_s
        for key, entry in (wire.get("h") or {}).items():
            name, _, label = key.partition("|")
            if name == "sched_tick_seconds":
                take_tick(
                    label.partition("=")[2] or label,
                    float(entry.get("n", 0)),
                    float(entry.get("s", 0.0)),
                )
            elif name == "obs_loop_lag_seconds":
                take_lag(
                    label.partition("=")[2] or label,
                    float(entry.get("n", 0)),
                    float(entry.get("s", 0.0)),
                    float(entry.get("max", 0.0) or 0.0),
                )
            elif name == "transport_serialize_seconds":
                take_serialize(
                    _parse_series_labels(label), float(entry.get("s", 0.0))
                )
            elif key == "worker_frame_step_seconds|step=device_wait":
                device_s += float(entry.get("s", 0.0))
        for key, value in (wire.get("c") or {}).items():
            name, _, label = key.partition("|")
            if name == "obs_loop_blocked_episodes_total":
                found = True
                role = label.partition("=")[2] or label
                episode_roles[role] = episode_roles.get(role, 0.0) + float(value)
            elif name == "transport_message_bytes_total":
                take_wire_bytes(_parse_series_labels(label), float(value))

    _consume_metric_snapshots(metrics, take_registry, take_wire)
    if not found:
        return None

    # The tick's dispatch phase already spans its in-tick RPC awaits; the
    # off-tick dispatch_rpc_await/dispatch_serialize observations only
    # price the control plane when no scheduler loop ran (single-job).
    control_s = tick_phases.get("total", {}).get("sum_s", 0.0)
    if control_s <= 0.0:
        control_s = sum(
            entry["sum_s"]
            for phase, entry in tick_phases.items()
            if phase in ("dispatch_rpc_await", "dispatch_serialize")
        )

    loop_lag: dict[str, Any] = {}
    for role, entry in sorted(lag_roles.items()):
        samples = entry["samples"]
        loop_lag[role] = {
            "samples": int(samples),
            "mean_lag_s": (entry["sum_s"] / samples) if samples else 0.0,
            "max_lag_s": entry["max_s"],
            "blocked_episodes": int(episode_roles.get(role, 0.0)),
        }
    for role, count in sorted(episode_roles.items()):
        loop_lag.setdefault(
            role,
            {"samples": 0, "mean_lag_s": 0.0, "max_lag_s": 0.0,
             "blocked_episodes": int(count)},
        )

    top = [
        {"tag": tag, **{k: row[k] for k in
                        ("bytes", "send_bytes", "recv_bytes", "serialize_s")}}
        for tag, row in talker_rows.items()
    ]
    top.sort(key=lambda row: row["bytes"], reverse=True)

    from tpu_render_cluster.analysis.attribution import attribution_report

    tick_section: dict[str, Any] | None = None
    if tick_phases:
        tick_section = {
            "ticks": int(tick_phases.get("total", {}).get("count", 0)),
            "phases": {
                phase: {"count": int(entry["count"]),
                        "sum_s": round(entry["sum_s"], 6)}
                for phase, entry in sorted(tick_phases.items())
            },
        }
    return attribution_report(
        critical_sections=critical_sections,
        worker_seconds=worker_seconds,
        device_seconds=device_s,
        transport_seconds=transport_s,
        control_seconds=control_s,
        tick=tick_section,
        loop_lag=loop_lag or None,
        top_talkers=top[:8] or None,
    )


_CHAOS_LEDGER_COUNTERS = (
    "master_frame_results_total",
    "master_duplicate_results_total",
    "master_late_results_total",
    "master_stale_results_total",
    "master_worker_evictions_total",
    "master_worker_drains_total",
)


def accumulate_chaos_fault_counts(
    registry_snapshot: dict[str, Any], into: dict[str, float]
) -> dict[str, float]:
    """Fold one registry snapshot's ``chaos_faults_injected_total`` series
    into ``into`` keyed by fault kind. Single definition site — the chaos
    runner's live report and this module's statistics.json section must
    parse the series labels identically."""
    entry = registry_snapshot.get("chaos_faults_injected_total")
    if entry:
        for label, value in entry.get("series", {}).items():
            kind = label.partition("=")[2] or label
            into[kind] = into.get(kind, 0.0) + float(value)
    return into


def summarize_chaos(metrics: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Roll the fault-injection evidence up (chaos/ engine artifacts).

    Aggregates ``chaos_faults_injected_total`` (what was done to the
    cluster) across every registry family a snapshot carries, plus the
    master's exactly-once ledger counters (what the cluster did about it).
    None when no snapshot shows any injected fault — ordinary runs get no
    ``chaos`` section even though the ledger counters exist.
    """
    faults: dict[str, float] = {}
    ledger: dict[str, dict[str, float]] = {}

    def take_registry(names: dict[str, Any]) -> None:
        accumulate_chaos_fault_counts(names, faults)
        for counter in _CHAOS_LEDGER_COUNTERS:
            counter_entry = names.get(counter)
            if not counter_entry:
                continue
            sink = ledger.setdefault(counter, {})
            for label, value in counter_entry.get("series", {}).items():
                sink[label or "total"] = sink.get(label or "total", 0.0) + float(
                    value
                )

    for snapshot in metrics:
        take_registry(snapshot.get("metrics", {}))
        for worker_registry in (snapshot.get("workers") or {}).values():
            if isinstance(worker_registry, dict):
                take_registry(worker_registry)
    if not faults:
        return None
    return {"faults_injected": faults, "ledger": ledger}


def summarize_obs(
    traces: list[ObsTrace],
    metrics: list[dict[str, Any]],
    cluster_traces: list[ObsTrace] | None = None,
    flight_bundles: list[dict[str, Any]] | None = None,
) -> dict[str, Any]:
    """Roll obs artifacts into a ``statistics.json``-shaped summary.

    ``cluster_traces`` (the merged clock-corrected timelines from
    ``load_cluster_traces``) additionally contribute a ``critical_path``
    section — per-run makespan critical path, per-worker idle attribution,
    and straggler scores (``analysis/critical_path.py``) — keyed by the
    run's file stem. ``flight_bundles`` (``load_blackbox_bundles``) fold
    into the ``history`` section's post-mortem ledger.
    """
    span_counts: dict[str, int] = {}
    durations: dict[str, list[float]] = {}
    for trace in traces:
        for cat, count in trace.span_count_by_category().items():
            span_counts[cat] = span_counts.get(cat, 0) + count
        for name, values in trace.span_seconds_by_name().items():
            durations.setdefault(name, []).extend(values)
    span_stats = {}
    for name, values in sorted(durations.items()):
        values = sorted(values)
        span_stats[name] = {
            "count": len(values),
            "total_s": sum(values),
            "p50_s": _percentile(values, 0.50),
            "p95_s": _percentile(values, 0.95),
            "max_s": values[-1],
        }
    out: dict[str, Any] = {
        "trace_event_files": len(traces),
        "metrics_snapshot_files": len(metrics),
        "spans_by_category": span_counts,
        "span_duration_stats": span_stats,
    }
    launch_occupancy = summarize_launch_occupancy(metrics)
    if launch_occupancy is not None:
        out["launch_occupancy"] = launch_occupancy
    chaos = summarize_chaos(metrics)
    if chaos is not None:
        out["chaos"] = chaos
    tiles = summarize_tiles(metrics)
    if tiles is not None:
        out["tiles"] = tiles
    sched = summarize_sched(metrics)
    if sched is not None:
        out["sched"] = sched
    prediction = summarize_prediction(metrics)
    if prediction is not None:
        out["prediction"] = prediction
    slo = summarize_slo(metrics)
    if slo is not None:
        out["slo"] = slo
    history = summarize_history(metrics, flight_bundles)
    if history is not None:
        out["history"] = history
    if cluster_traces:
        from tpu_render_cluster.analysis.critical_path import (
            summarize_critical_path,
        )

        sections = {}
        for trace in cluster_traces:
            section = summarize_critical_path(trace.events)
            if section is not None:
                sections[trace.path.stem] = section
        if sections:
            out["critical_path"] = sections
    attribution = summarize_attribution(
        metrics, out.get("critical_path")
    )
    if attribution is not None:
        out["attribution"] = attribution
    return out
