"""The arithmetic of ``train_rows_per_s`` on synthetic series: every row of
the window over every second of it, with the median cycle beside it."""

import pytest

from benchmarks import cycles


def test_serves_per_cycle_matches_the_buffers_trigger():
    # buffer_mult 32 at batch 4096, seq 1024: 128 sequences of 1023 rows
    assert cycles.serves_per_cycle(128 * 1023, 4096) == 15
    assert cycles.serves_per_cycle(8 * 64 // 16 * 16, 64) == 4


def test_a_stall_costs_the_rate_what_it_cost_the_job():
    steady = [2.18] * 18
    stalled = list(steady)
    stalled[4] += 1.0
    stalled[11] += 0.6
    a = cycles.rate(steady, 4096 * 15, 1)
    b = cycles.rate(stalled, 4096 * 15, 1)
    assert a["rows_per_s"] == pytest.approx(4096 * 15 / 2.18)
    # all rows over all seconds: 1.6 s of stall in 39.24 s of work
    assert b["rows_per_s"] == pytest.approx(a["rows_per_s"] * 39.24 / 40.84)
    assert b["window_s"] == pytest.approx(40.84)
    # the median cycle does not see it, which is why it is only a per-layer reading
    assert a["rows_per_s_median_cycle"] == b["rows_per_s_median_cycle"]
    assert a["cycle_s_median"] == b["cycle_s_median"] == 2.18
    assert b["cycle_max_over_median"] == pytest.approx(3.18 / 2.18)
    assert a["ok"] and b["ok"]


def test_a_uniformly_slower_run_reads_slower():
    a = cycles.rate([2.0] * 12, 1000, 1)
    b = cycles.rate([2.1] * 12, 1000, 1)
    assert b["rows_per_s"] == pytest.approx(a["rows_per_s"] / 1.05)


@pytest.mark.parametrize("n", [0, 1, 9])
def test_fewer_than_ten_cycles_is_refused(n):
    assert not cycles.rate([2.0] * n, 1000, 1)["ok"]
    assert cycles.rate([2.0] * 10, 1000, 1)["ok"]
    # a mix whose cycles are long names its own floor
    assert cycles.rate([11.0] * n, 1000, 1, min_cycles=3)["ok"] == (n >= 3)


def test_rate_is_per_chip():
    one = cycles.rate([1.0] * 10, 4000, 1)["rows_per_s"]
    four = cycles.rate([1.0] * 10, 4000, 4)["rows_per_s"]
    assert one == 4000 and four == 1000


def test_summary_counts_least_median_greatest():
    assert cycles.summary([3.0, 1.0, 2.0]) == {"n": 3, "min": 1.0, "median": 2.0, "max": 3.0}
    assert cycles.summary([]) == {"n": 0}
