"""The throughput estimator on synthetic completion times."""

import random

import pytest

from benchmark.lib.estimator import per_second, slope_rate


def bursty(period, burst, spacing, start, end):
    """Bursts of `burst` completions `spacing` apart, every `period` seconds."""
    times, t = [], start
    while t < end:
        times += [t + k * spacing for k in range(burst) if t + k * spacing < end]
        t += period
    return times


def test_uniform_stream_gives_its_rate():
    times = [0.05 * k for k in range(1, 900)]
    assert slope_rate(times) == pytest.approx(20.0, rel=1e-6)


def test_jittered_stream_is_close_to_its_rate():
    rng = random.Random(4)
    times = [0.05 * k + rng.uniform(-0.01, 0.01) for k in range(1, 900)]
    assert slope_rate(times) == pytest.approx(20.0, rel=2e-3)


@pytest.mark.parametrize("lull", [0.3, 2.0, 5.0, 9.0])
@pytest.mark.parametrize("period,burst", [(10.4, 8), (5.6, 4)])
def test_whole_bursts_give_their_rate_wherever_in_the_lull_the_window_starts(lull, period, burst):
    # the window begins `lull` seconds after a burst has ended, as the driver makes it
    start = 0.013 * burst + lull
    times = [t for t in bursty(period, burst, 0.013, 0.0, 80.0) if start < t <= start + 45.0]
    assert slope_rate(times) == pytest.approx(burst / period, rel=0.01)


def test_a_window_that_begins_inside_a_burst_reads_low():
    # why the driver waits for a lull: the tail of a burst drags the fit down
    whole = bursty(10.4, 8, 0.013, 10.3, 44.0)
    assert slope_rate([0.0, 0.02, 0.03] + whole) < 0.98 * 8 / 10.4


def test_counting_between_the_edges_swings_by_a_burst():
    counts = [
        len([t for t in bursty(10.4, 8, 0.013, 0.0, 80.0) if phase < t <= phase + 45.0]) / 45.0
        for phase in (0.2, 4.1, 9.9)
    ]
    assert max(counts) / min(counts) > 1.2  # what the estimator replaces


@pytest.mark.parametrize("stall_at", [12.0, 22.0, 33.0])
def test_a_stall_shows(stall_at):
    times = [0.035 * k for k in range(1, 1300)]
    stalled = [t if t < stall_at else t + 1.5 for t in times if t < stall_at or t + 1.5 <= 45.0]
    assert slope_rate(stalled) < 0.98 / 0.035


def test_too_few_points_give_nothing():
    assert slope_rate([1.0, 2.0]) is None
    assert slope_rate([1.0, 1.0, 1.0]) is None


def test_per_second_counts_every_completion_of_the_window():
    times = [100.0 + t for t in bursty(10.4, 8, 0.013, 0.5, 45.0)]
    counts = per_second(times, 100.0, 45.0)
    assert len(counts) == 45 and sum(counts) == len(times)
    assert counts[0] == 8 and counts[1] == 0 and counts[10] == 8
