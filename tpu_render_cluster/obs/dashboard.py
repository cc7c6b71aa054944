"""Live terminal dashboard over the pull-based telemetry endpoints.

``python -m tpu_render_cluster.obs.dashboard --port <telemetryPort>``
polls a master's ``/metrics`` (Prometheus text exposition, parsed with
``obs.prometheus.parse_prometheus``) and ``/clusterz`` (the live
``cluster_view()``) and redraws a one-screen operator view:

- cluster totals + per-worker queue depth;
- per-job progress and achieved-vs-target fair share;
- unit-latency percentiles reconstructed from the
  ``master_unit_latency_seconds`` histogram buckets;
- the speculation and assembly ledgers;
- SLO attainment/burn per job and the most recent alert edges;
- sparkline columns over the embedded metrics history (``/history``,
  obs/history.py): per-interval unit-completion rate and queue depth,
  so a stall or burst is visible as a *shape*, not one number;
- a "where did the time go" panel from the attribution families:
  sched-tick phase cost (``sched_tick_seconds{phase}``), event-loop lag
  per role (``obs_loop_lag_seconds``), and the wire's top talkers by
  ``transport_message_bytes_total{tag,direction}``;
- an HA section when the endpoint is the shard router's federated view
  (ha/shards.py): per-shard routed requests, ledger append p99
  (``ha_ledger_append_seconds``), and last-failover MTTR.

Stdlib-only (urllib + ANSI clears), like the rest of ``obs``: the
dashboard must run on any operator box that can reach the master, with
nothing installed. All rendering is pure (``render_dashboard``) so the
tier-1 tests exercise it against canned endpoint payloads; ``--once``
prints a single frame and exits (scripts, smoke tests).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Any, Iterable

from tpu_render_cluster.obs.prometheus import parse_prometheus

__all__ = [
    "fetch_endpoints",
    "fetch_history",
    "histogram_quantiles",
    "render_dashboard",
    "sparkline",
    "main",
]

Samples = dict[str, list[tuple[dict[str, str], float]]]

_CLEAR = "\x1b[2J\x1b[H"

# History series the dashboard sparklines by default: the unit-completion
# counter (rendered as per-interval rate) and the queue-depth gauge.
HISTORY_NAMES = (
    "master_frame_results_total",
    "master_worker_queue_depth",
)

_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def sparkline(values: list[float], width: int = 32) -> str:
    """Unicode block sparkline over ``values`` (newest right), resampled
    to ``width`` columns; a flat series renders as a flat low line."""
    if not values:
        return ""
    if len(values) > width:
        # Keep the newest `width` points — the dashboard shows recency.
        values = values[-width:]
    lo, hi = min(values), max(values)
    span = hi - lo
    if span <= 0:
        return _SPARK_BLOCKS[0] * len(values)
    top = len(_SPARK_BLOCKS) - 1
    return "".join(
        _SPARK_BLOCKS[int(round((v - lo) / span * top))] for v in values
    )


def fetch_endpoints(
    host: str, port: int, timeout: float = 5.0
) -> tuple[Samples, dict[str, Any]]:
    """One poll: parsed ``/metrics`` samples + the ``/clusterz`` JSON.

    A worker endpoint (no cluster view, /clusterz is 404) yields an empty
    dict for the second element rather than failing the poll.
    """
    base = f"http://{host}:{port}"
    with urllib.request.urlopen(f"{base}/metrics", timeout=timeout) as resp:
        metrics = parse_prometheus(resp.read().decode("utf-8"))
    try:
        with urllib.request.urlopen(f"{base}/clusterz", timeout=timeout) as resp:
            clusterz = json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as e:
        if e.code != 404:
            raise
        clusterz = {}
    return metrics, clusterz


def fetch_history(
    host: str,
    port: int,
    names: Iterable[str] = HISTORY_NAMES,
    timeout: float = 5.0,
) -> dict[str, Any]:
    """Range series for each ``name`` from ``/history`` (absent store —
    a pre-history master, a 404 — yields an empty dict, never a failed
    poll)."""
    out: dict[str, Any] = {}
    for name in names:
        url = (
            f"http://{host}:{port}/history?name="
            f"{urllib.parse.quote(name)}"
        )
        try:
            with urllib.request.urlopen(url, timeout=timeout) as response:
                document = json.loads(response.read().decode("utf-8"))
        except urllib.error.HTTPError as e:
            if e.code != 404:
                raise
            return {}
        if document.get("ok") and document.get("series"):
            out[name] = document
    return out


def histogram_quantiles(
    samples: Samples,
    name: str,
    quantiles: Iterable[float],
    where: dict[str, str] | None = None,
) -> dict[float, float] | None:
    """Quantile estimates from a histogram's ``_bucket`` expansion.

    The classic cumulative-bucket walk with linear interpolation inside
    the landing bucket (what promql's histogram_quantile does); the +Inf
    bucket clamps to the previous finite bound. Buckets with differing
    labels (multi-series histograms) are summed — the dashboard shows the
    cluster-wide distribution — unless ``where`` narrows them (the HA
    section computes per-shard percentiles from federated samples this
    way). Returns None when the histogram is absent or empty.
    """
    rows = samples.get(f"{name}_bucket")
    if not rows:
        return None
    by_bound: dict[float, float] = {}
    for labels, value in rows:
        if where and any(labels.get(k) != v for k, v in where.items()):
            continue
        le = labels.get("le")
        if le is None:
            continue
        bound = float("inf") if le == "+Inf" else float(le)
        by_bound[bound] = by_bound.get(bound, 0.0) + value
    bounds = sorted(by_bound)
    if not bounds:
        return None
    total = by_bound[bounds[-1]]
    if total <= 0:
        return None
    out: dict[float, float] = {}
    for q in quantiles:
        rank = q * total
        previous_bound = 0.0
        previous_count = 0.0
        for bound in bounds:
            count = by_bound[bound]
            if count >= rank:
                if bound == float("inf"):
                    out[q] = previous_bound
                elif count == previous_count:
                    out[q] = bound
                else:
                    fraction = (rank - previous_count) / (count - previous_count)
                    out[q] = previous_bound + fraction * (bound - previous_bound)
                break
            previous_bound, previous_count = bound, count
        else:
            # Rank past every bucket (float noise in the cumulative sums):
            # clamp to the largest FINITE bound. A degenerate histogram
            # whose only bucket is +Inf yields no estimate for this
            # quantile rather than an "inf" row.
            finite = [b for b in bounds if b != float("inf")]
            if finite:
                out[q] = finite[-1]
    return out or None


def _sample_value(
    samples: Samples, name: str, **labels: str
) -> float | None:
    for sample_labels, value in samples.get(name, ()):
        if all(sample_labels.get(k) == v for k, v in labels.items()):
            return value
    return None


def _fmt_seconds(value: float | None) -> str:
    if value is None or not math.isfinite(value):
        return "-"
    if value < 1e-3:
        return f"{value * 1e6:.0f}us"
    if value < 1.0:
        return f"{value * 1e3:.1f}ms"
    return f"{value:.2f}s"


def _fmt_share(value: Any) -> str:
    return f"{value:.2f}" if isinstance(value, (int, float)) else "-"


def _history_sparkline_rows(history: dict[str, Any]) -> list[str]:
    """Sparkline rows from /history range responses: counters render as
    per-interval deltas (the *rate* shape), gauges as raw values."""
    rows: list[str] = []
    for name, document in sorted(history.items()):
        kind = document.get("kind")
        for label_str, series in sorted((document.get("series") or {}).items()):
            values = [float(v) for v in series.get("v") or []]
            if not values:
                continue
            if kind == "counter":
                values = [
                    b - a for a, b in zip(values, values[1:])
                ] or values
                suffix = f"rate~{values[-1]:g}/t" if values else ""
            else:
                suffix = f"last={values[-1]:g}"
            label = f"{name}{{{label_str}}}" if label_str else name
            rows.append(f"{label:<44.44} {sparkline(values):<32} {suffix}")
    return rows


def _fmt_bytes(value: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if value < 1024 or unit == "GiB":
            return f"{value:.0f}{unit}" if unit == "B" else f"{value:.1f}{unit}"
        value /= 1024
    return f"{value:.1f}GiB"  # unreachable; keeps the signature total


def load_sched_bench(path: str | None = None) -> dict[str, Any] | None:
    """The committed control-plane A/B record
    (``results/SCHED_BENCH.json``, a CPU record of heap against scan tick
    mode; nothing in the repo writes it any more, ROADMAP D5), or None
    when absent/unreadable — the dashboard must render fine without it."""
    if path is None:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            "results",
            "SCHED_BENCH.json",
        )
    try:
        with open(path, "r", encoding="utf-8") as f:
            record = json.load(f)
    except (OSError, ValueError):
        return None
    return record if isinstance(record, dict) else None


def _render_time_section(
    samples: Samples, sched_bench: dict[str, Any] | None = None
) -> list[str]:
    """The "where did the time go" panel: sched-tick phase costs, event
    loop lag per role, the wire's top talkers — all reconstructed from
    the attribution metric families, all optional (a pre-PR-16 endpoint
    or an idle cluster just renders nothing here) — plus, when a
    committed ``results/SCHED_BENCH.json`` exists, the before/after
    control-plane A/B (assignments/s and share_scan p99 per tick mode)."""
    lines: list[str] = []

    phases = sorted(
        {
            labels.get("phase", "")
            for labels, _value in samples.get("sched_tick_seconds_count", ())
        }
        - {""}
    )
    phase_rows: list[str] = []
    for phase in phases:
        count = sum(
            value
            for labels, value in samples.get("sched_tick_seconds_count", ())
            if labels.get("phase") == phase
        )
        if count <= 0:
            continue
        total = sum(
            value
            for labels, value in samples.get("sched_tick_seconds_sum", ())
            if labels.get("phase") == phase
        )
        quantiles = histogram_quantiles(
            samples, "sched_tick_seconds", (0.5, 0.99), where={"phase": phase}
        ) or {}
        phase_rows.append(
            f"{phase:<20} {count:>7.0f} {_fmt_seconds(total / count):>9} "
            f"{_fmt_seconds(quantiles.get(0.5)):>9} "
            f"{_fmt_seconds(quantiles.get(0.99)):>9}"
        )
    if phase_rows:
        lines.append("")
        lines.append(
            f"{'sched tick phase':<20} {'ticks':>7} {'mean':>9} "
            f"{'p50':>9} {'p99':>9}"
        )
        lines.extend(phase_rows)
        budget = _sample_value(samples, "sched_tick_budget_ratio")
        if budget is not None and math.isfinite(budget):
            lines.append(f"tick budget used: {budget:.2f}x")

    roles = sorted(
        {
            labels.get("role", "")
            for labels, _value in samples.get("obs_loop_lag_seconds_count", ())
        }
        - {""}
    )
    lag_rows: list[str] = []
    for role in roles:
        count = sum(
            value
            for labels, value in samples.get("obs_loop_lag_seconds_count", ())
            if labels.get("role") == role
        )
        if count <= 0:
            continue
        quantiles = histogram_quantiles(
            samples, "obs_loop_lag_seconds", (0.99,), where={"role": role}
        ) or {}
        episodes = sum(
            value
            for labels, value in samples.get(
                "obs_loop_blocked_episodes_total", ()
            )
            if labels.get("role") == role
        )
        lag_rows.append(
            f"{role:<12} {count:>7.0f} {_fmt_seconds(quantiles.get(0.99)):>9} "
            f"{episodes:>8.0f}"
        )
    if lag_rows:
        lines.append("")
        lines.append(
            f"{'loop lag':<12} {'samples':>7} {'p99':>9} {'blocked':>8}"
        )
        lines.extend(lag_rows)

    by_tag: dict[str, dict[str, float]] = {}
    for labels, value in samples.get("transport_message_bytes_total", ()):
        tag = labels.get("tag", "?")
        entry = by_tag.setdefault(tag, {"send": 0.0, "recv": 0.0})
        direction = labels.get("direction", "send")
        entry[direction if direction in entry else "send"] += value
    talkers = sorted(
        by_tag.items(), key=lambda kv: -(kv[1]["send"] + kv[1]["recv"])
    )[:5]
    if talkers:
        lines.append("")
        lines.append(
            f"{'wire top talkers':<36} {'send':>10} {'recv':>10}"
        )
        for tag, entry in talkers:
            lines.append(
                f"{tag:<36.36} {_fmt_bytes(entry['send']):>10} "
                f"{_fmt_bytes(entry['recv']):>10}"
            )

    if sched_bench:
        rows: list[str] = []
        for mode in ("scan", "heap"):
            entry = sched_bench.get(mode)
            if not isinstance(entry, dict):
                continue
            rate = entry.get("assignments_per_s")
            p99 = entry.get("share_scan_p99_s")
            rows.append(
                f"{str(entry.get('tick_mode', mode)):<32.32} "
                f"{rate if rate is not None else '-':>9} "
                f"{_fmt_seconds(p99):>9}"
            )
        if rows:
            lines.append("")
            lines.append(
                f"{'sched A/B (SCHED_BENCH.json)':<32} {'assign/s':>9} "
                f"{'scan p99':>9}"
            )
            lines.extend(rows)
            speedup = sched_bench.get("speedup_assignments_per_s")
            if isinstance(speedup, (int, float)):
                lines.append(
                    f"speedup {speedup:.2f}x @ "
                    f"{sched_bench.get('jobs', '?')} concurrent jobs"
                )
    return lines


def _ha_shard_ids(samples: Samples) -> list[str]:
    """Shard ids present in the federated HA families ('all' fan-out rows
    excluded — they aggregate, they aren't a shard)."""
    shards: set[str] = set()
    for name in (
        "ha_router_requests_total",
        "ha_router_jobs_routed_total",
        "ha_router_scrapes_total",
        "ha_ledger_append_seconds_count",
        "ha_failover_mttr_seconds",
    ):
        for labels, _value in samples.get(name, ()):
            shard = labels.get("shard")
            if shard is not None and shard != "all":
                shards.add(shard)
    return sorted(shards, key=lambda s: (len(s), s))


def _render_ha_section(samples: Samples) -> list[str]:
    shards = _ha_shard_ids(samples)
    if not shards:
        return []
    lines = ["", f"{'HA shard':<9} {'requests':>8} {'jobs':>5} "
                 f"{'append p99':>11} {'last MTTR':>10}"]
    for shard in shards:
        requests = sum(
            value
            for labels, value in samples.get("ha_router_requests_total", ())
            if labels.get("shard") == shard
        )
        jobs = sum(
            value
            for labels, value in samples.get("ha_router_jobs_routed_total", ())
            if labels.get("shard") == shard
        )
        append_quantiles = histogram_quantiles(
            samples,
            "ha_ledger_append_seconds",
            (0.99,),
            where={"shard": shard},
        )
        mttr = _sample_value(
            samples, "ha_failover_mttr_seconds", shard=shard
        )
        lines.append(
            f"{'s' + shard:<9} {requests:>8.0f} {jobs:>5.0f} "
            f"{_fmt_seconds(append_quantiles.get(0.99) if append_quantiles else None):>11} "
            f"{_fmt_seconds(mttr):>10}"
        )
    return lines


def render_dashboard(
    samples: Samples,
    clusterz: dict[str, Any],
    *,
    history: dict[str, Any] | None = None,
    now: float | None = None,
    sched_bench: dict[str, Any] | None = None,
) -> str:
    """One dashboard frame as plain text (pure: canned payloads in, text
    out — the tests and --once path share it with the live loop)."""
    lines: list[str] = []
    cluster = clusterz.get("cluster") or {}
    stamp = time.strftime(
        "%H:%M:%S", time.localtime(now if now is not None else time.time())
    )
    lines.append(f"tpu-render-cluster telemetry  [{stamp}]")
    lines.append("=" * 72)

    frames_total = cluster.get("frames_total", 0)
    frames_finished = cluster.get("frames_finished", 0)
    frames_pending = cluster.get("frames_pending", 0)
    lines.append(
        f"units: {frames_finished}/{frames_total} finished, "
        f"{frames_pending} pending"
    )

    workers = cluster.get("workers") or {}
    if workers:
        lines.append("")
        lines.append(f"{'worker':<28} {'queue':>5} {'stolen':>6}  state")
        for worker_id, info in sorted(workers.items()):
            state = "DEAD" if info.get("is_dead") else "live"
            lines.append(
                f"{worker_id:<28} {info.get('queue_depth', 0):>5} "
                f"{info.get('frames_stolen', 0):>6}  {state}"
            )

    jobs = clusterz.get("jobs") or {}
    if jobs:
        lines.append("")
        lines.append(
            f"{'job':<24} {'state':<9} {'done':>9} "
            f"{'share':>6} {'target':>6}"
        )
        for name, info in sorted(jobs.items()):
            done = f"{info.get('frames_finished', 0)}/{info.get('frames_total', 0)}"
            lines.append(
                f"{name:<24} {str(info.get('state', '-')):<9} {done:>9} "
                f"{_fmt_share(info.get('share_achieved')):>6} "
                f"{_fmt_share(info.get('share_target')):>6}"
            )

    quantiles = histogram_quantiles(
        samples, "master_unit_latency_seconds", (0.5, 0.9, 0.99)
    )
    if quantiles:
        lines.append("")
        lines.append(
            "unit latency  p50 "
            f"{_fmt_seconds(quantiles.get(0.5))}   p90 "
            f"{_fmt_seconds(quantiles.get(0.9))}   p99 "
            f"{_fmt_seconds(quantiles.get(0.99))}"
        )

    speculation = clusterz.get("speculation") or {}
    if speculation.get("launched"):
        outcomes = speculation.get("outcomes") or {}
        lines.append(
            f"speculation   launched {speculation['launched']}  "
            + "  ".join(f"{k} {v}" for k, v in sorted(outcomes.items()))
        )

    assembled = [
        (name, info["assembly"])
        for name, info in sorted(jobs.items())
        if isinstance(info.get("assembly"), dict)
    ]
    for name, assembly in assembled:
        lines.append(
            f"assembly      {name}: {assembly.get('frames_assembled', 0)} "
            f"stitched, {assembly.get('frames_partial', 0)} partial "
            f"({assembly.get('tiles_per_frame', 1)} tiles/frame)"
        )

    slo = clusterz.get("slo") or {}
    slo_jobs = slo.get("jobs") or {}
    if slo_jobs:
        lines.append("")
        lines.append(
            f"{'SLO job':<24} {'attain':>7} {'burn_s':>7} {'burn_l':>7}  firing"
        )
        for name, info in sorted(slo_jobs.items()):
            attainment = info.get("attainment")
            attain_str = f"{attainment:.3f}" if attainment is not None else "-"
            burn = info.get("burn") or {}
            firing = ",".join(info.get("firing") or ()) or "-"
            lines.append(
                f"{name:<24} {attain_str:>7} "
                f"{burn.get('short', 0.0):>7.2f} "
                f"{burn.get('long', 0.0):>7.2f}  {firing}"
            )
    alerts = slo.get("alerts") or []
    for alert in alerts[-5:]:
        at = time.strftime("%H:%M:%S", time.localtime(alert.get("at", 0)))
        lines.append(
            f"alert  [{at}] {alert.get('job_name')} {alert.get('kind')} "
            f"{str(alert.get('transition', '')).upper()}"
        )

    lines.extend(_render_time_section(samples, sched_bench=sched_bench))
    lines.extend(_render_ha_section(samples))

    if history:
        rows = _history_sparkline_rows(history)
        if rows:
            lines.append("")
            lines.append("history")
            lines.extend(rows)

    flight = clusterz.get("flight") or {}
    if flight.get("triggers"):
        lines.append(
            "flight rec    "
            + "  ".join(
                f"{trigger} {count}"
                for trigger, count in sorted(flight["triggers"].items())
            )
            + f"  ({len(flight.get('dumps') or [])} bundle(s))"
        )

    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="Live terminal dashboard over the telemetry endpoints"
    )
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument(
        "--port", type=int, required=True,
        help="The master's --telemetryPort (or TRC_OBS_PORT)",
    )
    parser.add_argument("--interval", type=float, default=1.0)
    parser.add_argument(
        "--once", action="store_true",
        help="Print one frame and exit (scripts, smoke tests)",
    )
    args = parser.parse_args(argv)
    sched_bench = load_sched_bench()  # static artifact: load once, not per frame
    while True:
        try:
            samples, clusterz = fetch_endpoints(args.host, args.port)
            try:
                history = fetch_history(args.host, args.port)
            except (OSError, urllib.error.URLError, ValueError):
                history = {}  # sparklines degrade; the snapshot view stays
        except (OSError, urllib.error.URLError, ValueError) as e:
            frame = f"telemetry endpoint unreachable: {e}\n"
        else:
            frame = render_dashboard(
                samples, clusterz, history=history, sched_bench=sched_bench
            )
        if args.once:
            sys.stdout.write(frame)
            return 0
        sys.stdout.write(_CLEAR + frame)
        sys.stdout.flush()
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
