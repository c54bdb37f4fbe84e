"""Algorithm 1's tier-capacity peak check and whole-construction parity.

``_peak_with`` below is the scalar definition the array store
(``repro.core.greedy.TierIntervals``) must reproduce to the bit: a list of
events rebuilt, sorted and swept per query. The golden fixture holds
``construct_greedy`` and ``load_balance`` answers recorded with that
list-based implementation, before the store replaced it.
"""
import json
import pathlib

import numpy as np
import pytest

from repro.core.greedy import (
    PEAK_QUERIES,
    STRATEGIES,
    TierIntervals,
    _exact_sizes,
    construct_greedy,
)
from repro.core.load_balance import load_balance
from repro.instances.suites import load_npz

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def _peak_with(intervals: list[list[float]], birth: float, size: float) -> float:
    """Peak usage over [birth, ∞) if a block of ``size`` is added at ``birth``."""
    events: list[tuple[float, float]] = [(birth, size)]
    for b, e, s in intervals:
        if e <= birth:
            continue
        events.append((max(b, birth), s))
        if np.isfinite(e):
            events.append((e, -s))
    events.sort(key=lambda t: (t[0], t[1]))
    run = peak = 0.0
    for _, delta in events:
        run += delta
        peak = max(peak, run)
    return peak


def _bits(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float64).view(np.int64)


def _random_tier(rng, exact: bool, n: int):
    """A store and the same intervals as lists, on a coarse time grid so that
    births, deaths and query starts tie often."""
    grid = np.arange(12.0) * 2.5
    if exact:
        sizes = rng.integers(1, 20, size=n).astype(np.float64)
    else:
        sizes = rng.integers(1, 20, size=n) * 0.1 + rng.random(n) * 1e-3
    tier = TierIntervals(exact)
    lists = []
    for s in sizes:
        b = float(rng.choice(grid))
        tier.add(b, float(s))
        lists.append([b, np.inf, float(s)])
    for k in rng.permutation(n)[: n * 2 // 3]:   # the rest stay alive forever
        b = lists[k][0]
        e = b if rng.random() < 0.2 else float(rng.choice(grid[grid >= b]))
        tier.set_death(int(k), e)
        lists[k][1] = e
    return tier, lists, grid, sizes


@pytest.mark.parametrize("exact", [True, False], ids=["integer", "fractional"])
@pytest.mark.parametrize("seed", range(6))
def test_peaks_match_list_sweep_bit_for_bit(exact, seed):
    rng = np.random.default_rng(seed)
    tier, lists, grid, sizes = _random_tier(rng, exact, n=int(rng.integers(0, 60)))
    for _ in range(40):
        # starts on the grid (ties with births and deaths, frees at exactly
        # the start) and between it
        starts = np.concatenate([rng.choice(grid, 4), rng.random(2) * 30.0])
        size = float(rng.choice(sizes)) if len(sizes) else 3.0
        tentative = [[float(x) for x in rng.choice(sizes if len(sizes) else [1.0],
                                                     rng.integers(0, 3))]
                     for _ in starts]
        got = tier.peaks(starts, size, tentative)
        want = [_peak_with(lists + [[st, np.inf, t] for t in tent], st, size)
                for st, tent in zip(starts, tentative)]
        np.testing.assert_array_equal(_bits(got), _bits(want))
        # a later commit or death update is seen by the next query
        k = int(rng.integers(0, 100))
        if k < len(lists) and np.isinf(lists[k][1]):
            e = float(rng.choice(grid[grid >= lists[k][0]]))
            tier.set_death(k, e)
            lists[k][1] = e
        elif k % 3 == 0:
            b, s = float(rng.choice(grid)), float(rng.choice(sizes)) if len(sizes) else 1.0
            tier.add(b, s)
            lists.append([b, np.inf, s])


def test_peaks_on_an_empty_tier():
    for exact in (True, False):
        got = TierIntervals(exact).peaks(np.array([0.0, 4.0]), 7.0, [[], [2.0]])
        np.testing.assert_array_equal(got, [7.0, 9.0])


@pytest.mark.parametrize("sizes, exact", [
    ([1.0, 15000.0, 3.0], True),
    ([0.0, 2.0], True),
    ([1.5, 2.0], False),
    ([-1.0, 2.0], False),
    ([np.inf, 2.0], False),
    ([np.nan, 2.0], False),
    ([2.0**52, 1.0], False),
])
def test_exact_sizes(sizes, exact):
    assert _exact_sizes(np.array(sizes)) is exact


_INSTANCES = load_npz(str(FIXTURES / "greedy_golden_instances.npz"))
_GOLDEN = json.loads((FIXTURES / "greedy_golden_solutions.json").read_text())["cases"]


@pytest.mark.parametrize(
    "case", _GOLDEN,
    ids=[f"{_INSTANCES[c['instance']].name}-{c['method']}-{c['seed']}" for c in _GOLDEN])
def test_construction_matches_golden(case):
    """Roomy, 20%-tight, FFT and fractional-size instances (40 tasks, and
    250 tasks for roomy and tight): every strategy and ``load_balance`` gives
    the recorded ``assign``, ``mem`` and ``proc_seq``."""
    inst = _INSTANCES[case["instance"]]
    if case["method"] == "load_balance":
        sol = load_balance(inst, rng=case["seed"])
    else:
        sol = construct_greedy(inst, case["method"], rng=case["seed"])
    assert sol.assign.tolist() == case["assign"]
    assert sol.mem.tolist() == case["mem"]
    assert [[int(t) for t in seq] for seq in sol.proc_seq] == case["proc_seq"]


def test_golden_covers_every_strategy_and_both_paths():
    methods = {c["method"] for c in _GOLDEN}
    assert methods == set(STRATEGIES) | {"load_balance"}
    assert {_exact_sizes(i.data_size) for i in _INSTANCES} == {True, False}


def test_peak_counter_advances_per_query():
    before = PEAK_QUERIES.copy()
    TierIntervals(True).peaks(np.array([0.0, 1.0, 2.0]), 1.0, [[], [], []])
    TierIntervals(False).peaks(np.array([0.0, 1.0]), 1.0, [[], []])
    delta = PEAK_QUERIES - before
    assert delta == {"queries": 5, "incremental": 3, "per_query": 2}


@pytest.mark.parametrize("name, path", [
    ("tight40", "incremental"), ("roomy250", "incremental"),
    ("tight40_nonint", "per_query"),
])
def test_incremental_path_engages_on_integer_sizes_only(name, path):
    inst = next(i for i in _INSTANCES if i.name == name)
    before = PEAK_QUERIES.copy()
    construct_greedy(inst, "slack_first")
    load_balance(inst)
    delta = PEAK_QUERIES - before
    assert delta["queries"] > 0
    assert delta[path] == delta["queries"]
