"""The benchmark's backlog rule, counted in tier-1.

`benchmark/tests/test_backlog_rule.py` (pure Python, no process started, two
seconds in all) holds each backlog configuration's smallest backlog to the
rate it states and `drivers/backlog.py::child_exit` to what a child's exit
means. The driver's tier-1 command collects `tests/` alone, and that rule
is what lets a faster program be measured at all (PERF.md §7 i), so its
cases are brought in here under their own names.

Since PR 52 the backlog driver runs a sixth configuration,
`04vs-14400f-1w-png`. The benchmark's file lists five, and only a
`benchmark` PR may edit it (PERF.md §7), so the case that holds the list is
held here on the list as it is now, and the new configuration goes through
the file's own two cases under names of its own.

Since PR 56 it runs a seventh, `02phmesh-240f-1w` (its two cases are in
`tests/test_shallow_mesh_job.py`, with the rest of what holds that job).
"""

from benchmark.lib import manifest
from benchmark.tests import test_backlog_rule as rule
from benchmark.tests.test_backlog_rule import *  # noqa: F401,F403
from benchmark.tests.test_backlog_rule import BACKLOG_CONFIGS, BENCHMARK, ROOT

PNG_CONFIG = "04vs-14400f-1w-png"
SHALLOW_MESH_CONFIG = "02phmesh-240f-1w"
# the JPEG configuration's job and span, so its backlog and its floor; the rate is the one
# PERF.md §5 reads in `04vs-1w-png` (my chip runs, PR 52), 1/26 of what the backlog holds
PNG_ROW = (13681, 290.0, [11.21])


def test_these_are_the_configurations_the_backlog_driver_runs():  # noqa: F811
    driven = {
        cell.config_name
        for cell in (manifest.load_cell(workload["name"], ROOT) for workload in BENCHMARK["workloads"])
        if cell.traffic["driver"] == "backlog"
    }
    assert driven == set(BACKLOG_CONFIGS) | {PNG_CONFIG, SHALLOW_MESH_CONFIG}


def test_the_png_configurations_smallest_backlog_holds_the_rate_it_states(monkeypatch):
    monkeypatch.setitem(BACKLOG_CONFIGS, PNG_CONFIG, PNG_ROW)
    rule.test_the_smallest_backlog_holds_the_rate_the_configuration_states(PNG_CONFIG)
    assert rule.config_of(PNG_CONFIG)["holds_frames_per_s"] == rule.config_of("04vs-14400f-1w")["holds_frames_per_s"]


def test_a_seeds_first_frame_lies_inside_the_png_configurations_span(tmp_path):
    rule.test_a_seeds_first_frame_lies_inside_the_span(PNG_CONFIG, tmp_path)
