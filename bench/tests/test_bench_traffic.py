"""The traffic generator: deterministic per seed, true to its file, and the
same work for every seed in another order."""
import statistics
from collections import Counter

import traffic


def test_files_load_by_name():
    for name in ("burst", "closed8"):
        spec = traffic.load(name)
        assert spec["name"] == name and spec["loop"] in ("open", "closed")


def test_open_loop_is_deterministic_per_seed():
    spec = traffic.load("burst")
    a = traffic.open_loop(spec, 2**31 + 11, 75.0, 151936)
    b = traffic.open_loop(spec, 2**31 + 11, 75.0, 151936)
    c = traffic.open_loop(spec, 2**31 + 12, 75.0, 151936)
    assert a == b
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_every_seed_gets_the_same_work_in_another_order():
    spec = traffic.load("burst")
    a = traffic.open_loop(spec, 1, 68.0, 1000)
    b = traffic.open_loop(spec, 5_000_000_000, 68.0, 1000)
    assert len(a) == len(b)
    assert [x.due for x in a] != [x.due for x in b]
    assert Counter(len(x.prompt) for x in a) == Counter(len(x.prompt)
                                                        for x in b)
    assert Counter(x.max_new_tokens for x in a) == Counter(
        x.max_new_tokens for x in b)
    # each stretch of each period gets the same count and the same lengths
    cuts = traffic.stretches(spec["arrivals"], 68.0)

    def stretch(x):
        return next(j for j, (lo, hi, _) in enumerate(cuts)
                    if lo <= x.due < hi)
    for j in range(len(cuts)):
        assert Counter(len(x.prompt) for x in a if stretch(x) == j) == \
            Counter(len(x.prompt) for x in b if stretch(x) == j)
        assert Counter(x.max_new_tokens for x in a if stretch(x) == j) == \
            Counter(x.max_new_tokens for x in b if stretch(x) == j)


def test_lengths_follow_the_file():
    spec = traffic.load("burst")
    n = 401
    p = traffic.quantile_lengths(spec["prompt"], n)
    o = traffic.quantile_lengths(spec["output"], n)
    assert statistics.median(p) == spec["prompt"]["median"]
    assert statistics.median(o) == spec["output"]["median"]
    assert min(p) >= spec["prompt"]["min"] and max(p) == spec["prompt"]["max"]
    assert min(o) >= spec["output"]["min"] and max(o) == spec["output"]["max"]
    items = traffic.open_loop(spec, 3, 300.0, 50)
    assert all(1 <= t < 50 for x in items for t in x.prompt)


def test_arrivals_follow_the_rate_and_its_spikes():
    arr = dict(traffic.load("burst")["arrivals"], base_rate=2.0)
    horizon = 176 * arr["period_s"]
    ts = [t for t, _ in traffic.arrival_times(arr, horizon, 2**31 + 5)]
    assert abs(len(ts) - horizon * traffic.mean_rate(arr)) <= 1
    spike = sum(1 for t in ts if traffic.rate_at(t, arr) > arr["base_rate"])
    share = arr["spike_s"] / arr["period_s"]
    want = arr["spike_factor"] * share / (1 + (arr["spike_factor"] - 1)
                                          * share)
    assert abs(spike / len(ts) - want) < 0.01
    assert ts == sorted(ts) and 0.0 <= ts[0] and ts[-1] < horizon


def test_arrival_times_come_from_the_seed_inside_each_stretch():
    arr = traffic.load("burst")["arrivals"]
    a = traffic.arrival_times(arr, 68.0, 7)
    b = traffic.arrival_times(arr, 68.0, 2**33 + 7)
    assert a == traffic.arrival_times(arr, 68.0, 7) and a != b
    cuts = traffic.stretches(arr, 68.0)
    for got in (a, b):
        for t, j in got:
            assert cuts[j][0] <= t < cuts[j][1]
        assert Counter(j for _, j in got) == Counter(j for _, j in a)
    # every stretch is one constant rate, and they tile the horizon
    assert cuts[0][0] == 0.0 and cuts[-1][1] == 68.0
    assert all(x[1] == y[0] for x, y in zip(cuts, cuts[1:]))
    for lo, hi, rate in cuts:
        assert traffic.rate_at(lo + 1e-6, arr) == rate
        assert traffic.rate_at(hi - 1e-6, arr) == rate


def test_closed_loop_batches_are_distinct_and_repeatable():
    spec = traffic.load("closed8")
    b0 = traffic.closed_loop(spec, 9, 1000)
    b1 = traffic.closed_loop(spec, 9, 1000, block=1)
    assert len(b0) == spec["queue"] and b0 == traffic.closed_loop(spec, 9,
                                                                  1000)
    assert [x.index for x in b1] == list(range(len(b0), 2 * len(b0)))
    assert [x.prompt for x in b0] != [x.prompt for x in b1]
    assert all(x.due == 0.0 for x in b0)
