"""What a pool that lost a worker under load owes its users, and what of
that a run broke. No scheduler in it: plain sets and dicts.

One worker of the pool is killed without a word while jobs are in hand.
The farm owes its users: every job in hand at the kill still ends, inside
`settle_s`, with exactly its files; no unit the dead worker held is lost
(a survivor renders it after the kill and its file is whole); a unit is
rendered twice only for a cause the master states; and what the dead
worker's cut write left behind is no output.

What this is given is what a client and the file system can see, plus two
reports of the master's:

- `jobs`: the seeded job stream as the client submitted it, each
  `{"name", "first", "last", "submitted_at", "finished_at" (when the client
  saw it reported finished, else None), "files": {frame: mtime of its whole
  file}, "other_paths": [every path under its directory that is no frame of
  its range]}`;
- `kill`: `{"worker" (its id as the master writes it), "at"}` and `settle_s`;
- `survivors`: each surviving worker's own record, from the `render` spans
  of the timeline it exports: `{worker: [(job, frame, end of the span)]}`;
- `results`: the master's record of whose result finished which unit
  (`{"op": "results"}`): `[{"job_name", "frame", "worker"}]`. The dead
  worker left no timeline, so its share of the renders is what the master
  took from it;
- `handbacks`: the master's reports of units that left a worker without a
  result (`{"op": "handbacks"}`): `[{"job_name", "frame", "worker", "cause",
  "at"}]`.

`account` states what must hold and `problems` names what does not.
"""

from __future__ import annotations

import json
from pathlib import Path

from benchmark.reference import plain_pool

Unit = tuple[str, int]  # job name, frame
# The causes this configuration's guarantee names (a_rerender_states_its_cause):
# the pool configuration's six. What the dead worker held comes back as `eviction`.
CAUSES = plain_pool.CAUSES


def rendered_spans(timeline: Path) -> list[tuple[str, int, float]] | None:
    """(job, frame, wall time the span ended) of every frame the worker that
    wrote `timeline` rendered to the end; None where its spans name no job."""
    document = json.loads(Path(timeline).read_text())
    events = document["traceEvents"] if isinstance(document, dict) else document
    renders = [
        event for event in events
        if event.get("ph") == "X" and event.get("cat") == "worker" and event.get("name") == "render"
    ]
    if any("job" not in (event.get("args") or {}) for event in renders):
        return None
    return [
        (event["args"]["job"], int(event["args"]["frame"]), (event["ts"] + event["dur"]) / 1e6)
        for event in renders
    ]


def in_hand_at(jobs: list[dict], at: float) -> list[dict]:
    """The jobs with the service at `at`: submitted, and not yet seen finished."""
    return [
        job for job in jobs
        if job["submitted_at"] <= at and (job["finished_at"] is None or job["finished_at"] > at)
    ]


def account(
    jobs: list[dict], kill: dict, settle_s: float, survivors: dict[str, list],
    results: list[dict], handbacks: list[dict],
) -> dict:
    """What happened, laid out for the reader and for `problems`:
    `in_hand` (names), `stranded` (the units the master reports as having
    left the dead worker at its eviction, each `{"job", "frame", "back_at",
    "rendered_again_at", "file_at"}`), `rendered_twice` (`explained` /
    `unexplained`, as `plain_pool.account` splits them), `bad_causes`,
    `leavings`, `late` (jobs in hand not finished inside `settle_s`, with the
    frames of theirs that have no file)."""
    by_name = {job["name"]: job for job in jobs}
    deadline = kill["at"] + settle_s
    in_hand = in_hand_at(jobs, kill["at"])

    late = [
        {"job": job["name"], "finished_at": job["finished_at"],
         "missing": [f for f in range(job["first"], job["last"] + 1) if f not in job["files"]]}
        for job in in_hand
        if job["finished_at"] is None or job["finished_at"] > deadline
    ]

    again: dict[Unit, float] = {}  # the first survivor's render of the unit that ended after the kill
    for spans in survivors.values():
        for job, frame, ended_at in spans:
            if ended_at > kill["at"]:
                again[(job, frame)] = min(ended_at, again.get((job, frame), ended_at))
    # the dead worker's share: what the master took from it
    of_the_dead = [(r["job_name"], int(r["frame"])) for r in results if r["worker"] == kill["worker"]]

    stranded = []
    for report in handbacks:
        if report["worker"] != kill["worker"] or report["cause"] != "eviction":
            continue
        unit = (report["job_name"], int(report["frame"]))
        job = by_name.get(unit[0])
        stranded.append({
            "job": unit[0], "frame": unit[1], "back_at": report["at"],
            "rendered_again_at": again.get(unit),
            "file_at": job["files"].get(unit[1]) if job is not None else None,
        })

    # `plain_pool.account`'s rule over the survivors' records and the dead
    # worker's share: a unit rendered k times needs k - 1 stated causes.
    dead = f"{kill['worker']} (dead)"
    explained, unexplained = plain_pool.account(
        {**{worker: [span[:2] for span in spans] for worker, spans in survivors.items()}, dead: of_the_dead},
        handbacks,
    )
    bad_causes = [
        {"job": report["job_name"], "frame": int(report["frame"]), "cause": report.get("cause")}
        for report in handbacks if report.get("cause") not in CAUSES
    ]
    leavings = [
        {"job": job["name"], "path": path}
        for job in jobs if job["finished_at"] is not None for path in job["other_paths"]
    ]
    return {
        "in_hand": [job["name"] for job in in_hand], "stranded": stranded, "late": late,
        "rendered_twice": {"explained": explained, "unexplained": unexplained},
        "bad_causes": bad_causes, "leavings": leavings,
    }


def problems(accounted: dict, kill: dict, settle_s: float) -> list[str]:
    """Every breach of the four guarantees, each naming its job and unit."""
    out = []
    for job in accounted["late"]:
        seen = "never" if job["finished_at"] is None else f"{job['finished_at'] - kill['at']:.1f} s after the kill"
        out.append(
            f"{job['job']} was in hand at the kill and was reported finished {seen}, not inside "
            f"{settle_s:.0f} s of it; of its range, frames {job['missing'] or 'none'} have no file"
        )
    for unit in accounted["stranded"]:
        if unit["rendered_again_at"] is None:
            out.append(
                f"{unit['job']} frame {unit['frame']} was with the dead worker {kill['worker']} and no survivor "
                "rendered it after the kill"
            )
        elif unit["file_at"] is None:
            out.append(f"{unit['job']} frame {unit['frame']} was with the dead worker and has no whole file")
    for unit in accounted["rendered_twice"]["unexplained"]:
        out.append(
            f"{unit['job']} frame {unit['frame']} was rendered {unit['renders']} times ({', '.join(unit['by'])}) "
            f"and the master reports {unit['causes'] or 'no cause'}"
        )
    for report in accounted["bad_causes"]:
        out.append(
            f"{report['job']} frame {report['frame']} left a worker for {report['cause']!r}, a cause the "
            f"guarantee does not name ({', '.join(sorted(CAUSES))})"
        )
    for leaving in accounted["leavings"]:
        out.append(f"{leaving['job']} was reported finished and its directory holds {leaving['path']}, no frame of it")
    return out
