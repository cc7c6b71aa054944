"""`dispatch_ahead_frame_share`, counted in tier-1.

`benchmark/tests/test_dispatch_ahead_metric.py` (pure Python, no process
started) holds the metric's entry, its data file and the accepted
`delta_ratio` reader's three answers (no counter, no frames, a share). The
driver's tier-1 command collects `tests/` alone, and the metric is what
says on every ledger line whether dispatch-ahead engaged (PERF.md §3), so
its cases are brought in here under their own names, as
`tests/test_benchmark_backlog_rule.py` brings in the backlog rule.
"""

from benchmark.tests.test_dispatch_ahead_metric import *  # noqa: F401,F403
