"""The per-test time limit of tests/conftest.py, tried on a pytest child.

The child runs this repo's conftest.py over a file of four tests: one that
spins inside an event loop without ever yielding (what stalled the suite
before PR 28), two that show the alarm does not outlive its test, and one
that is cut while it holds a child process of its own.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHILD_TESTS = '''
import asyncio
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest


@pytest.fixture(autouse=True)
def nothing_armed_between_tests():
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) == signal.SIG_DFL
    yield
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) == signal.SIG_DFL


@pytest.mark.time_limit(2)
def test_spins_without_yielding():
    async def spin():
        done = asyncio.get_running_loop().create_future()
        done.set_result(None)
        while True:
            await asyncio.gather(done)  # the spinning line

    asyncio.run(spin())


@pytest.mark.time_limit(1)
def test_quick_under_a_short_limit():
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 1


def test_outlives_the_previous_tests_limit():
    assert 1 < signal.getitimer(signal.ITIMER_REAL)[0] <= 300
    time.sleep(1.5)


@pytest.mark.time_limit(2)
@pytest.mark.usefixtures("kill_leftover_children")
def test_cut_while_holding_a_child():
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    Path("child.pid").write_text(str(child.pid))
    child.wait(timeout=50)
'''


@pytest.fixture(scope="module")
def child_run(tmp_path_factory):
    directory = tmp_path_factory.mktemp("time_limit")
    shutil.copy(Path(__file__).with_name("conftest.py"), directory / "conftest.py")
    (directory / "test_child.py").write_text(CHILD_TESTS)
    result = subprocess.run(
        [sys.executable, "-m", "pytest", "test_child.py", "-v"]
        + ["-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=directory,
        capture_output=True,
        text=True,
        timeout=30,
    )
    return result, directory


def test_a_spinning_test_fails_at_its_limit_and_names_the_line(child_run):
    result, _ = child_run
    assert result.returncode == 1, result.stdout + result.stderr
    assert "test_spins_without_yielding FAILED" in result.stdout
    assert "ran past its time limit of 2 s" in result.stdout
    assert "await asyncio.gather(done)  # the spinning line" in result.stdout


def test_the_alarm_is_cleared_and_the_run_goes_on(child_run):
    result, _ = child_run
    assert "test_quick_under_a_short_limit PASSED" in result.stdout
    assert "test_outlives_the_previous_tests_limit PASSED" in result.stdout
    assert "2 failed, 2 passed" in result.stdout, result.stdout
    assert "ERROR" not in result.stdout, result.stdout  # the autouse checks


def test_a_test_cut_at_its_limit_leaves_no_child_behind(child_run):
    result, directory = child_run
    assert "test_cut_while_holding_a_child FAILED" in result.stdout
    pid = int((directory / "child.pid").read_text())
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)
