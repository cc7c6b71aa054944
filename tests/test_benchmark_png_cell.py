"""The cell `04vs-1w-png`, counted in tier-1.

`benchmark/tests/test_png_cell.py` holds the cell to what ISSUE 52 names
(configuration, traffic, chips, the counts from this PR on: ten cells,
three on four chips, nine configurations with nine sources and files), to
differing from `04vs-1w-coarse` by the output format alone, its check to a
lossless file's limits, and its three metrics to being data whose reader
gives nothing for a program without their series. The driver's tier-1
command collects `tests/` alone, so those cases (pure Python, but for one
`run.py --list`) are brought in here under their own names, as
`tests/test_benchmark_dispatch_ahead_metric.py` brings in its. The cell's
rehearsal starts processes and stays where it is, outside tier-1, as the
other cells' rehearsals do.
"""

from benchmark.tests.test_png_cell import (  # noqa: F401
    test_the_cell_is_data_and_says_what_the_issue_says,
    test_the_check_reads_a_lossless_file_without_a_codec_and_tighter_than_jpegs,
    test_the_readers_give_nothing_for_a_program_without_the_series_and_the_value_with_them,
    test_the_three_readers_are_data_and_read_the_series_the_issue_names,
    test_the_two_04vs_one_worker_cells_differ_by_the_output_format_alone,
)
