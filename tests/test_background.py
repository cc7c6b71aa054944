"""The completion barrier of the loop's background tasks
(utils/background.py), through its three entry points:
``FrameAssemblyService.drain`` / ``drain_job`` and ``FlightRecorder.drain``.

Deterministic: no thread (``asyncio.to_thread`` is replaced by an inline
twin), no sleep longer than one loop turn. Each case names the order of
loop turns it needs, so what it pins down is the barrier's contract, not
thread timing: none pending on return, and every pass yields to the loop.
"""

from __future__ import annotations

import asyncio

import pytest

from tpu_render_cluster.jobs.models import BlenderJob, DistributionStrategy
from tpu_render_cluster.master.assembly import FrameAssemblyService
from tpu_render_cluster.master.state import ClusterManagerState
from tpu_render_cluster.obs.flightrec import FlightRecorder
from tpu_render_cluster.obs.registry import MetricsRegistry

JOB_NAME = "drain-unit"


class _Inline:
    """Stands in for ``asyncio.to_thread``: the callee runs in the task's
    own step, after whatever the test queued for that call."""

    def __init__(self) -> None:
        self.calls = 0
        self.gates: list[asyncio.Event] = []
        self.fail = False

    async def __call__(self, function, /, *args, **kwargs):
        self.calls += 1
        if self.gates:
            await self.gates.pop(0).wait()
        if self.fail:
            raise RuntimeError("boom")
        return function(*args, **kwargs)


class _Assembly:
    """``start()`` schedules one more frame's stitch of JOB_NAME."""

    def __init__(self, tmp_path, per_job: bool) -> None:
        job = BlenderJob(
            job_name=JOB_NAME,
            job_description="drain unit test",
            project_file_path="%BASE%/p.blend",
            render_script_path="%BASE%/s.py",
            frame_range_from=1,
            frame_range_to=8,
            wait_for_number_of_workers=1,
            frame_distribution_strategy=DistributionStrategy.naive_fine(),
            output_directory_path=str(tmp_path),
            output_file_name_format="rendered-#####",
            output_file_format="PNG",
            tile_grid=(2, 2),
        )
        self.state = ClusterManagerState(job)
        self.service = FrameAssemblyService()
        self.per_job = per_job
        self.started = 0

    def start(self) -> None:
        self.started += 1
        self.service.schedule(self.state, self.started)

    def drain(self):
        if self.per_job:
            return self.service.drain_job(JOB_NAME)
        return self.service.drain()

    def empty(self) -> bool:
        return not self.service.has_pending(JOB_NAME)

    def accounted(self, inline: _Inline) -> bool:
        # _assemble's own except: an errored stitch still counts the frame.
        return self.state.frames_assembled == inline.calls


class _Recorder:
    """``start()`` fires one more trigger kind (each kind debounces itself)."""

    KINDS = ("worker_eviction", "job_failure", "epoch_fence", "slo_alert")

    def __init__(self, tmp_path) -> None:
        self.recorder = FlightRecorder(
            metrics=MetricsRegistry(), directory=tmp_path
        )
        self.started = 0

    def start(self) -> None:
        kind = self.KINDS[self.started]
        self.started += 1
        assert self.recorder.trigger(kind, {}) is not None

    def drain(self):
        return self.recorder.drain()

    def empty(self) -> bool:
        return not self.recorder._pending.pending()

    def accounted(self, inline: _Inline) -> bool:
        # Every trigger is in the dump ledger whether its write landed.
        return len(self.recorder.dumps) == self.started


@pytest.fixture(params=["assembly.drain", "assembly.drain_job", "flightrec.drain"])
def entry(request, tmp_path):
    if request.param == "flightrec.drain":
        return _Recorder(tmp_path)
    return _Assembly(tmp_path, per_job=request.param.endswith("drain_job"))


@pytest.fixture
def inline(monkeypatch):
    twin = _Inline()
    monkeypatch.setattr(asyncio, "to_thread", twin)
    return twin


async def _turns(n: int) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


async def _entered_as_the_only_task_finishes(entry, inline):
    # Both first steps are queued in one iteration: the task runs to its
    # end (inline), and the drain is entered in that same iteration — the
    # task done, its done-callback still queued. A wait that does not
    # yield here never lets the loop run again.
    entry.start()
    await asyncio.create_task(entry.drain())
    assert inline.calls == 1


async def _task_cancelled_before_its_first_step(entry, inline):
    before = asyncio.all_tasks()
    entry.start()
    (task,) = asyncio.all_tasks() - before
    task.cancel()
    await entry.drain()
    assert task.cancelled() and inline.calls == 0


async def _task_scheduled_while_the_drain_waits(entry, inline):
    first, second = asyncio.Event(), asyncio.Event()
    inline.gates = [first, second]
    entry.start()
    drain = asyncio.create_task(entry.drain())
    await _turns(3)
    entry.start()
    first.set()
    await _turns(6)
    assert not drain.done(), "returned with the second task still running"
    second.set()
    await drain
    assert inline.calls == 2


async def _task_that_raises(entry, inline):
    inline.fail = True
    entry.start()
    await _turns(1)  # the first has failed by the time the drain is entered,
    entry.start()  # the second fails under it
    await entry.drain()  # must not raise
    assert inline.calls == 2


@pytest.mark.time_limit(5)
@pytest.mark.parametrize(
    "case",
    [
        _entered_as_the_only_task_finishes,
        _task_cancelled_before_its_first_step,
        _task_scheduled_while_the_drain_waits,
        _task_that_raises,
    ],
    ids=lambda case: case.__name__.strip("_"),
)
def test_drain_returns_with_none_pending(entry, inline, case):
    async def run():
        await case(entry, inline)
        assert entry.empty()
        assert entry.accounted(inline)
        # A drain of nothing returns at once, too.
        await entry.drain()

    asyncio.run(run())
