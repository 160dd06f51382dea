"""Tail and rate arithmetic: over all requests and the whole window, with
unfinished requests counted as censored."""
import pytest

import stats
from stats import Record


def test_percentile_interpolates_between_order_statistics():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 0.5) == 3.0
    assert stats.percentile(xs, 0.95) == pytest.approx(4.8)
    assert stats.percentile(xs, 1.0) == 5.0
    assert stats.percentile([], 0.95) is None


def test_ttft_times_from_due_and_censors_at_the_close():
    recs = [Record(due=1.0, tokens=[1.5, 1.6]),      # 0.5 s
            Record(due=2.0, tokens=[9.0]),            # after the close
            Record(due=3.0),                          # never served
            Record(due=0.5, tokens=[1.2])]            # due before the window
    got = stats.ttft_samples(recs, 1.0, 5.0)
    assert got == pytest.approx([0.5, 3.0, 2.0])


def test_tbt_counts_every_gap_and_the_open_one():
    recs = [Record(due=0.0, tokens=[0.5, 1.5, 2.0, 4.0], finished=4.0),
            Record(due=0.0, tokens=[2.5, 3.0])]       # unfinished
    # window [1, 5): gaps ending in it are 1.0, 0.5, 2.0 and 0.5; the
    # second request is still in a gap of 2.0 at the close
    got = sorted(stats.tbt_samples(recs, 1.0, 5.0))
    assert got == pytest.approx([0.5, 0.5, 1.0, 2.0, 2.0])


def test_rate_is_over_the_whole_window():
    recs = [Record(due=0.0, tokens=[0.5, 1.0, 1.5]),
            Record(due=0.0, tokens=[3.9, 4.0])]
    assert stats.tokens_in(recs, 1.0, 4.0) == 3
    e2e = stats.end_to_end(recs, 1.0, 4.0)
    assert e2e["output_tokens_per_s"] == pytest.approx(1.0)


def test_a_stall_cannot_hide():
    fast = [Record(due=float(i), tokens=[i + 0.1]) for i in range(19)]
    stalled = fast + [Record(due=10.5)]
    a = stats.end_to_end(fast, 0.0, 20.0)["ttft_p95_ms"]
    b = stats.end_to_end(stalled, 0.0, 20.0)["ttft_p95_ms"]
    assert b > 5 * a
    assert stats.end_to_end(stalled, 0.0, 20.0)["censored_ttft"] == 1

