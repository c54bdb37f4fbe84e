"""Window arithmetic on synthetic completion timelines."""
import math

import pytest

from bench.window import Completion, Window, close_time, cuts, nearest_rank


def timeline(cut_times, per_cut=4, iters=1, latency=10.0, quality=1.25):
    out, rid = [], 0
    for k, t in enumerate(cut_times):
        for j in range(per_cut):
            out.append(Completion(rid=rid, sent=t - latency - j, done=t + 0.001 * j,
                                  cut=k, iterations=iters,
                                  mk_over_lb=quality + 0.01 * j))
            rid += 1
    return out


def test_cuts_group_by_launch_and_time_order():
    comps = timeline([30.0, 10.0, 20.0])
    got = cuts(comps)
    assert [t for t, _ in got] == [10.0, 20.0, 30.0]
    assert all(len(g) == 4 for _, g in got)


@pytest.mark.parametrize("cut_times,opened,seconds,want", [
    ([10.0, 31.0, 52.0, 73.0], 10.0, 51.0, 52.0),     # last cut in time
    ([10.0, 31.0, 62.0, 90.0], 10.0, 51.0, 31.0),     # 62 is past 10 + 51
    ([10.0, 70.0, 90.0], 10.0, 51.0, 70.0),           # none in time: the next
    ([10.0], 10.0, 51.0, None),                       # nothing after opening
    ([10.0, 61.0], 10.0, 51.0, 61.0),                 # a cut at the deadline
])
def test_close_time(cut_times, opened, seconds, want):
    assert close_time(cut_times, opened, seconds) == want


def test_rate_is_over_whole_cuts_in_the_window():
    comps = timeline([10.0, 31.0, 52.0, 73.0], iters=2)
    w = Window.of(comps, opened=10.0, closed=52.0)
    # the opening cut is outside, the two cuts after it inside
    assert len(w.completions) == 8 and w.n_cuts() == 2
    assert w.search_iters_per_s() == pytest.approx(8 * 2 / 42.0)


def test_p95_is_nearest_rank_over_every_request():
    assert nearest_rank(range(1, 21), 0.95) == 19
    assert nearest_rank(range(1, 101), 0.95) == 95
    assert nearest_rank([5.0], 0.95) == 5.0
    comps = timeline([10.0, 31.0, 52.0], latency=20.0)
    w = Window.of(comps, opened=10.0, closed=52.0)
    lat = sorted(c.latency for c in w.completions)
    assert w.latency_p95_s() == lat[math.ceil(0.95 * len(lat)) - 1] == lat[-1]


def test_quality_is_the_mean_over_answered_requests():
    comps = timeline([10.0, 31.0], quality=1.5)
    comps.append(Completion(rid=99, sent=20.0, done=31.0, cut=1,
                            error="LaunchFailure: lost"))
    w = Window.of(comps, opened=10.0, closed=31.0)
    assert len(w.completions) == 5 and len(w.answered) == 4
    assert w.mk_over_lb() == pytest.approx(1.5 + 0.015)
    assert w.search_iters_per_s() == pytest.approx(4 / 21.0)
