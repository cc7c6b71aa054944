"""The benchmark's backlog rule, counted in tier-1.

`benchmark/tests/test_backlog_rule.py` (pure Python, no process started, two
seconds in all) holds each backlog configuration's smallest backlog to the
rate it states and `drivers/backlog.py::child_exit` to what a child's exit
means. The driver's tier-1 command collects `tests/` alone, and that rule
is what lets a faster program be measured at all (PERF.md §7 i), so its
cases are brought in here under their own names.
"""

from benchmark.tests.test_backlog_rule import *  # noqa: F401,F403
