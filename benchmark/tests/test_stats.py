"""The arrival and percentile arithmetic, and the payments generator's
promises: the same seed gives the same traffic, every seed the same
amount of it."""

import math

import pytest

from benchmark.harness.stats import percentile, poisson_arrivals, rng_for
from benchmark.traffic.payments import Payments

OPEN = {"loop": "open", "rate_per_s": 50.0, "corrupt_every": 20,
        "submit_batch": 16}
CLOSED = {"loop": "closed", "clients": 10, "corrupt_every": 20,
          "submit_batch": 16}


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert percentile(v, 0.95) == 95 and percentile(v, 0.5) == 50
    assert percentile(v, 1.0) == 100 and percentile([7.0], 0.95) == 7.0
    # failed requests are +inf and sit in the tail
    assert percentile(v[:94] + [math.inf] * 6, 0.95) == math.inf
    assert percentile(v[:96] + [math.inf] * 4, 0.95) == 95
    with pytest.raises(ValueError):
        percentile([], 0.95)


def test_arrivals_same_count_for_every_seed_same_times_for_one():
    a = poisson_arrivals(rng_for(2 ** 31 + 7, "x"), 60.0, 30.0)
    b = poisson_arrivals(rng_for(2 ** 31 + 7, "x"), 60.0, 30.0)
    c = poisson_arrivals(rng_for(11, "x"), 60.0, 30.0)
    assert a == b and a != c and len(a) == len(c) == 1800
    assert a == sorted(a) and 0 <= a[0] and a[-1] < 30.0


def drain(gen, seconds):
    out, t = [], 0.0
    while t < seconds + 1:
        for r in gen.take_due(t, 16):
            out.append(r)
            gen.replied(r, t)
        t += 0.01
    return out


def test_open_loop_sends_every_arrival_and_a_fixed_share_corrupted():
    reqs = drain(Payments(OPEN, 5, 100, 4.0), 4.0)
    again = drain(Payments(OPEN, 5, 100, 4.0), 4.0)
    other = drain(Payments(OPEN, 6, 100, 4.0), 4.0)
    key = [(r.account, r.due, r.corrupt, r.amount, r.dest) for r in reqs]
    assert key == [(r.account, r.due, r.corrupt, r.amount, r.dest)
                   for r in again]
    assert len(reqs) == len(other) == 200
    assert sum(r.corrupt for r in reqs) == sum(r.corrupt for r in other) \
        == 10
    assert all(r.dest != r.account and 0 <= r.dest < 100 for r in reqs)


def test_open_loop_owes_late_arrivals_after_the_window():
    gen = Payments(OPEN, 5, 100, 2.0)
    early = gen.take_due(1.0, 10 ** 6)
    late = gen.take_due(2.0, 10 ** 6)      # the window has closed
    assert len(early) + len(late) == 100 and late
    assert all(r.due < 2.0 for r in late)


def test_closed_loop_stops_with_the_window():
    gen = Payments(CLOSED, 5, 100, 2.0)
    first = gen.take_due(0.0, 16)
    assert len(first) == 10 and not gen.take_due(0.5, 16)
    gen.replied(first[0], 0.6)
    again = gen.take_due(0.7, 16)
    assert [r.account for r in again] == [first[0].account]
    assert again[0].due == 0.6
    gen.replied(again[0], 1.9)
    assert gen.take_due(2.0, 16) == []
