"""Tasks a service starts on the running loop and must see finished.

The one completion barrier behind ``FrameAssemblyService.drain`` /
``drain_job`` and ``FlightRecorder.drain``. A task leaves the set in its
done-callback, which runs one loop turn AFTER the task finishes (and also
for a task cancelled before its first step), so the wait must suspend
whatever state the tasks are in: ``asyncio.gather`` over futures that are
all done completes eagerly on 3.12+, and a ``while`` around it spins with
the ``pop`` — and every timeout — queued behind it. ``asyncio.wait``
always suspends on a non-empty set.
"""

from __future__ import annotations

import asyncio
from collections.abc import Coroutine, Hashable


class BackgroundTasks:
    def __init__(self) -> None:
        self._tasks: dict[asyncio.Task, Hashable] = {}

    def spawn(self, coro: Coroutine, *, name: str, key: Hashable = None) -> None:
        task = asyncio.get_running_loop().create_task(coro, name=name)
        self._tasks[task] = key
        task.add_done_callback(self._tasks.pop)

    def pending(self, key: Hashable = None) -> list[asyncio.Task]:
        """Tasks whose done-callback has not run; with ``key``, only those
        spawned under it."""
        return [t for t, k in self._tasks.items() if key is None or k == key]

    async def drain(self, key: Hashable = None) -> None:
        """Return once ``pending(key)`` is empty, tasks spawned meanwhile
        included. Every pass gives the loop a turn; no task's exception is
        raised here, and cancelling the drain cancels no task."""
        while tasks := self.pending(key):
            await asyncio.wait(tasks)
