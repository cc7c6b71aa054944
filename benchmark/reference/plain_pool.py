"""Which (job, frame) units a pool rendered more than once, and which of
those the master can account for. No scheduler in it: plain sets.

A pool owes its users each frame of each job rendered once. A frame may
be rendered twice for a cause the master states: it took the frame back
from a worker (a preemption, a steal), the worker died or left (an
eviction, a drain), the frame errored or its dispatch failed, and the
worker had the frame in hand all the same. A frame two workers rendered
for no stated cause is the scheduler handing one unit out twice.

From outside the scheduler there are two records:

- each worker's own: the `render` spans of the timeline it exports as it
  drains (`cat: "worker"`, `args.job` and `args.frame`, one span a frame
  it rendered to the end; a program whose spans name no job has no such
  record, and `rendered_units` says so by returning None);
- the master's: what it reports over the control plane of every unit that
  left a worker without a result (`{"op": "handbacks"}`: job, frame, cause).

`account` lays one over the other. A unit rendered k times needs k - 1
reports, each with one of the causes above (`CAUSES`); what it returns
beyond that is for the reader: who rendered what.
"""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

Unit = tuple[str, int]  # job name, frame
# The causes the guarantee names; a report with any other, or none, explains nothing.
CAUSES = frozenset({"preemption", "steal", "eviction", "drain", "error", "dispatch_failed"})


def rendered_units(timeline: Path) -> list[Unit] | None:
    """The (job, frame) of every frame the worker that wrote `timeline`
    rendered to the end, in order; None where its spans name no job."""
    document = json.loads(timeline.read_text())
    events = document["traceEvents"] if isinstance(document, dict) else document
    renders = [
        event for event in events
        if event.get("ph") == "X" and event.get("cat") == "worker" and event.get("name") == "render"
    ]
    if any("job" not in (event.get("args") or {}) for event in renders):
        return None
    return [(event["args"]["job"], int(event["args"]["frame"])) for event in renders]


def account(rendered: dict[str, list[Unit]], reported: list[dict]) -> tuple[list[dict], list[dict]]:
    """(`explained`, `unexplained`): the units rendered more than once,
    each `{"job", "frame", "renders", "by", "causes"}`, split by whether
    the master's reports (`job_name`, `frame`, `cause`) cover every render
    but one. `rendered` maps a worker's name to its `rendered_units`."""
    renders: Counter[Unit] = Counter()
    by: dict[Unit, list[str]] = {}
    for worker, units in sorted(rendered.items()):
        for unit in units:
            renders[unit] += 1
            by.setdefault(unit, []).append(worker)
    causes: dict[Unit, list[str]] = {}
    for report in reported:
        if report.get("cause") in CAUSES:
            causes.setdefault((report["job_name"], int(report["frame"])), []).append(report["cause"])
    explained, unexplained = [], []
    for unit, count in sorted(renders.items()):
        if count < 2:
            continue
        stated = causes.get(unit, [])
        entry = {"job": unit[0], "frame": unit[1], "renders": count, "by": by[unit], "causes": stated}
        (explained if len(stated) >= count - 1 else unexplained).append(entry)
    return explained, unexplained
