"""`walk_top_tests_per_entry`, counted in tier-1.

`benchmark/tests/test_walk_top_tests_metric.py` (pure Python, no process
started) holds the metric's entry, its file and its reader's answers: nothing
without the walk's four counters or without an entry, 4.4 on the binary
top's counts of PERF.md §5 and 1.4 on a wide top's. The driver's tier-1
command collects `tests/` alone, and the metric is what says on the two
streamed cells' ledger lines how often the top's step runs for each treelet
it finds (PERF.md §3), so its cases are brought in here under their own
names, as `tests/test_benchmark_dispatch_ahead_metric.py` brings in its.
"""

from benchmark.tests.test_walk_top_tests_metric import *  # noqa: F401,F403
