"""The dispatch loops' wake-up: a result that leaves a worker with nothing
queued behind the frame it is rendering starts the next pass at once.

A dispatch loop that refills shallow queues (``naive_fine_strategy``: one
frame at a time; ``JobManager._scheduler_loop``: a queue of 2) used to
sleep a whole tick between passes, so a frame that ended at a random point
of the tick left its worker dry for half a tick on average. Such a loop
now waits on this object instead, with its tick as the timeout: a lost
signal costs what a tick cost before.

**Who sets it** (``WorkerHandle``, and the manager when a worker
connects): a finished event after which that worker's mirrored queue
holds at most one frame — empty, or only the frame the worker takes in
hand next, whether or not its rendering event has arrived yet — and a
worker's job-ready event. The rule reads the queue's depth, not the
strategy's name: under a queue of 4 or 100 it is never met, and the loops
that keep such queues (eager-naive-coarse, dynamic, tpu-batch) do not
wait on it and keep their ticks.

**Coalescing**: it is one ``asyncio.Event``. A burst of results while the
loop waits is one early pass; a result that lands while a pass is under
way starts one more pass after it. (``naive_fine_strategy``'s pass awaits
its queue-add RPCs; the service's pass only picks and claims, and its
queue-adds go out on a task a worker beside it, ``JobManager._send_claims``.)

``trigger`` is the kind of the pass under way, ``"event"`` or ``"tick"``
(every pass of a loop that never waits here is a tick).
``master_dispatch_frames_total{trigger}`` counts the frames handed to
workers by the kind of the pass that CLAIMED them, once their queue-add is
acknowledged: a pass whose queue-adds outlive it (the service's) hands its
kind along with the claim, since ``wait()`` may have started the next pass
by the time the acknowledgement lands; both label values exist at zero
from the start.
"""

from __future__ import annotations

import asyncio

TRIGGERS = ("event", "tick")
# At most this many frames in a worker's mirror after a finished event
# means nothing is queued behind the frame it renders (or takes next).
SHALLOW_QUEUE = 1


class DispatchWakeup:
    def __init__(self, metrics=None) -> None:
        self._event = asyncio.Event()
        self.trigger = "tick"
        self._dispatched = None
        if metrics is not None:
            self._dispatched = metrics.counter(
                "master_dispatch_frames_total",
                "Frames handed to a worker (queue-add acknowledged), by the "
                "kind of dispatch pass that handed them: woken by a worker "
                "event, or by the loop's tick",
                labels=("trigger",),
            )
            for trigger in TRIGGERS:
                self._dispatched.inc(0.0, trigger=trigger)

    def set(self) -> None:
        self._event.set()

    def is_set(self) -> bool:
        return self._event.is_set()

    async def wait(self, tick_seconds: float) -> str:
        """Sleep until the signal or for one tick, whichever is first;
        returns (and keeps as ``trigger``) which of the two it was."""
        try:
            await asyncio.wait_for(self._event.wait(), tick_seconds)
        except asyncio.TimeoutError:
            pass
        self.trigger = "event" if self._event.is_set() else "tick"
        self._event.clear()
        return self.trigger

    def count_dispatched_frame(self, trigger: str | None = None) -> None:
        """``trigger``: the kind of the pass that claimed the frame, where
        that pass may be over by now; else the pass under way."""
        if self._dispatched is not None:
            self._dispatched.inc(trigger=trigger or self.trigger)
