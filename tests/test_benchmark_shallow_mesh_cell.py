"""The cells `04vs-1w-fine` and `02phmesh-1w-queued`, counted in tier-1.

`benchmark/tests/test_shallow_mesh_cell.py` holds the two cells to what
ISSUE 56 names (configuration, traffic, chips, their places behind the ten
cells that were there), `04vs-1w-fine` to differing from `04vs-1w-coarse`
by its traffic alone, the two names to being appended to the accepted
lists the issue names and to no other, the configuration
`02phmesh-240f-1w` to its source, cuts, assumptions, guarantees and
limits, and `mesh_fused_frame_share` to being data whose reader gives
nothing for a program without the series. The driver's tier-1 command
collects `tests/` alone, so those cases (pure Python, but for one
`run.py --list`) are brought in here under their own names, as
`tests/test_benchmark_png_cell.py` brings in its. The cell's rehearsal
starts processes and stays where it is, outside tier-1, as the other
cells' rehearsals do.
"""

from benchmark.tests.test_shallow_mesh_cell import (  # noqa: F401
    test_both_cells_are_data_and_say_what_the_issue_says,
    test_the_check_reads_two_frames_of_bodies_in_the_air_on_crops_that_hold_them,
    test_the_configuration_states_its_source_its_cuts_and_what_it_assumes,
    test_the_fine_cell_differs_from_the_coarse_cell_by_its_traffic_alone,
    test_the_metric_is_data_and_reads_nothing_for_a_program_without_the_series,
    test_the_two_cells_are_appended_to_the_accepted_lists_and_nothing_else_moved,
)
