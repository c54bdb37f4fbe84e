"""The readers of Algorithm 3's span and of the two program counters:
``alg3_s``, ``alg3_refused_pct`` and ``repair_fallback_pct``."""
import collections
import importlib
import shutil
import types
from pathlib import Path

import pytest

from bench import spans, trace
from bench.spans import Event

FIXTURES = Path(__file__).resolve().parent / "fixtures"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
S = 1e9  # ns per second


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}")


def op(start, dur):
    return Event(DEV, "XLA Ops", "while.1", start * S, dur * S,
                 "jit(one)/vmap(ts_round)/while")


def span(name, start, dur, cut=3):
    return Event(HOST, "serve-solve_0", name, start * S, dur * S, cut=cut)


def two_launches(alg3):
    """A stretch of 20 s with two launches, and ``alg3``'s spans."""
    return [
        Event(HOST, "python", "bench.traced", 0.0, 20 * S),
        op(3, 8), op(13, 5),
        span("repro.engine.execute", 0, 20),
        span("repro.search.prep", 0, 2.9),
        span("repro.search.launch", 2.9, 0.1),
        span("repro.search.sync", 11, 1.9),
        span("repro.search.launch", 12.9, 0.1),
        span("repro.search.finish", 18, 1),
    ] + [span("repro.search.alg3", s, d) for s, d in alg3]


def test_alg3_seconds_are_a_union_in_the_stretch_per_launch():
    events = two_launches([
        (-2, 2.5),    # began before the stretch: 0.5 s of it inside
        (1, 1),       # overlaps the next one: 1.0 to 2.5 counted once
        (1.5, 1),
        (11, 1.5),
        (18, 0.5),
        (25, 1),      # after the stretch
    ])
    assert reader("alg3_s").per_launch(events) == pytest.approx(
        (0.5 + 1.5 + 1.5 + 0.5) / 2)


def test_alg3_seconds_without_spans_launches_or_device_are_none():
    per_launch = reader("alg3_s").per_launch
    assert per_launch(two_launches([])) is None               # the parent
    no_device = [e for e in two_launches([(1, 1)]) if e.plane != DEV]
    assert per_launch(no_device) is None                      # a CPU run
    no_launch = [e for e in two_launches([(1, 1)])
                 if e.name != "repro.search.launch"]
    assert per_launch(no_launch) is None
    assert per_launch([]) is None


def test_alg3_reader_finds_the_run_s_own_trace(tmp_path, monkeypatch):
    """From the run as ``bench/run.py`` builds it: a recorded trace with no
    Algorithm 3 span reads None, and so does a run without a trace."""
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    d = tmp_path / "cell" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(FIXTURES / "spans_trace.xplane.pb", d / "run.xplane.pb")
    reduced = trace.reduce(trace.load(str(tmp_path / "cell")))
    read = reader("alg3_s").read
    assert read(types.SimpleNamespace(trace=reduced)) is None
    assert read(types.SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("name, module, counter, value", [
    ("alg3_refused_pct", "repro.core.memory_update", "ALG3",
     collections.Counter(calls=4, blocks=200, refused=50, evicted=3)),
    ("repair_fallback_pct", "repro.core.device_search", "REPAIRS",
     collections.Counter(walks=16, infeasible=8, fallback=2)),
])
def test_counter_readers_take_the_whole_run_ratio(name, module, counter, value,
                                                   monkeypatch):
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, counter, value)
    assert reader(name).read(None) == pytest.approx(25.0)
    monkeypatch.setattr(mod, counter, collections.Counter())
    assert reader(name).read(None) is None        # nothing placed or infeasible
    monkeypatch.delattr(mod, counter)
    assert reader(name).read(None) is None        # a program without the counter


def test_counter_ratios_on_their_own():
    c = collections.Counter
    assert reader("alg3_refused_pct").pct(c(blocks=8, refused=8)) == 100.0
    assert reader("alg3_refused_pct").pct(c(calls=2)) is None
    assert reader("repair_fallback_pct").pct(c(infeasible=4)) == 0.0
    assert reader("repair_fallback_pct").pct(c(walks=16)) is None
