"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs the multichip
path; the benchmark, ``benchmark/run.py``, runs on the real chip).

Must run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import signal
import subprocess
import sys
import threading
from pathlib import Path

import pytest

# Make the repo root importable regardless of pytest invocation directory.
REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

# Every test's own time limit: a hang (or a coroutine that spins without
# ever yielding) costs that one test, not the suite's whole clock.
# ``@pytest.mark.time_limit(seconds)`` gives a test another.
TEST_TIME_LIMIT_SECONDS = 300.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Fail the test from a SIGALRM handler when its limit runs out.

    Tests run in the main thread of their process (xdist workers too), so
    the handler's exception lands in whatever line that thread is running
    — the failure's traceback names it — even inside an event loop that
    never gets a turn. Children a test started are its own cleanup's to
    kill (``subprocess.run`` does on any exception). The alarm is cleared
    and the previous handler restored after every test, pass or fail.
    """
    if (
        not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        return (yield)
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_TIME_LIMIT_SECONDS

    def on_alarm(signum, frame):
        pytest.fail(f"{item.nodeid} ran past its time limit of {limit:g} s")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def kill_leftover_children(monkeypatch):
    """Kill what the test started and did not reap, however it ended.

    For tests that hold daemons in bare ``subprocess.Popen`` objects: a
    failed assertion or the time limit above would otherwise leave them
    running (and holding their ports) under the rest of the run.
    """
    started = []

    class TrackedPopen(subprocess.Popen):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            started.append(self)

    monkeypatch.setattr(subprocess, "Popen", TrackedPopen)
    yield
    for process in started:
        if process.poll() is None:
            process.kill()
            process.wait()


@pytest.fixture(autouse=True)
def _isolated_process_global_stores():
    """Reset process-global stores whose contents would otherwise depend
    on which tests ran earlier in this worker."""
    # The host-side geometry-build memo (render/mesh.py): BVH/
    # TLAS builds are pure, but per-test build-count assertions (e.g.
    # render_tlas_builds_total deltas) must not depend on which
    # hierarchies earlier tests already built.
    mesh = sys.modules.get("tpu_render_cluster.render.mesh")
    if mesh is not None:
        mesh.reset_geometry_cache()
    # The start-up recorder (obs/startup.py): process-scoped, and its marks
    # are set once, so every test's workers begin a start-up of their own.
    startup = sys.modules.get("tpu_render_cluster.obs.startup")
    if startup is not None:
        startup.reset_startup()
    yield


@pytest.fixture
def startup_timeline():
    """A tracer the process's start-up recorder writes through to, as a
    worker's would be: the stages, the ``bvh_build`` spans and JAX's
    ``render.compile`` spans of this test land in its events."""
    from tpu_render_cluster.obs import MetricsRegistry, Tracer, get_startup

    tracer = Tracer("startup-under-test")
    get_startup().attach(tracer, MetricsRegistry())
    return tracer
