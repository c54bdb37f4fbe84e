"""The reduction from the program's spans and scopes to the per-layer
metrics that read them."""
import dataclasses
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
LOOP = "jit(one)/vmap(ts_round)/while"   # as a v5e trace names it
READERS = ("search_prep_s", "sync_host_s", "round_device_s", "exact_eval_s",
           "idle_in_execute_pct", "idle_in_assemble_pct")


def op(name, start, dur, stack="", plane=DEV):
    return Event(plane, "XLA Ops", name, start * S, dur * S, stack)


def span(name, start, dur, line="serve-solve_0", cut=None, rid=None):
    return Event(HOST, line, name, start * S, dur * S, cut=cut, rid=rid)


def read(name, events):
    run = types.SimpleNamespace(spans=spans.reduce(events),
                                trace=trace.reduce(events))
    return importlib.import_module(f"bench.metrics.{name}").read(run)


def one_cut():
    """A stretch of 20 s holding one launch: the loop's while op encloses
    its body's ops, two exact-eval ops overlap, an upload runs after the
    loop, and two assemble spans overlap."""
    return [
        span("bench.traced", 0, 20, line="python"),
        op("while.1", 4, 10, LOOP),
        op("fusion.2", 5, 2, LOOP + "/body/ts_exact_eval/dot"),
        op("fusion.3", 6, 2, LOOP + "/body/ts_exact_eval/while"),
        op("fusion.4", 8, 1, LOOP + "/body/ts_move_gen/sort:"),
        op("copy.5", 15, 0.5, "jit(one)/copy"),
        span("repro.engine.assemble", 0, 3, line="serve-dispatch"),
        span("repro.engine.assemble", 2, 1.5, line="serve-dispatch"),
        span("repro.engine.assemble", 16.5, 2.5, line="serve-dispatch"),
        span("repro.engine.execute", 1, 15),
        span("repro.search.prep", 1, 2.9),
        span("repro.search.launch", 3.9, 0.1),
        span("repro.search.readback", 4, 10),
        span("repro.search.sync", 14, 1.8),
        span("repro.search.finish", 15.8, 0.1),
        span("repro.engine.fanout", 15.9, 0.05),
        span("repro.engine.fanout", 15.95, 0.05),
    ]


def test_idle_is_put_down_to_execute_to_assemble_or_to_neither():
    events = one_cut()
    # busy [4, 14] and [15, 15.5]: idle [0, 4], [14, 15], [15.5, 20]
    # in execute [1, 16]: [1, 4], [14, 15], [15.5, 16] = 4.5 s of 20
    assert read("idle_in_execute_pct", events) == pytest.approx(22.5)
    # outside it, under assemble [0, 3.5] and [16.5, 19]: [0, 1], [16.5, 19]
    assert read("idle_in_assemble_pct", events) == pytest.approx(17.5)
    # neither: [16, 16.5] and [19, 20]; the three add up to the idle share
    assert read("device_idle_share", events) == pytest.approx(22.5 + 17.5 + 7.5)


def test_overlapping_spans_are_counted_once():
    events = one_cut() + [span("repro.engine.execute", 2, 3, line="serve-solve_1"),
                          span("repro.search.sync", 14.5, 1, line="serve-solve_1")]
    assert read("idle_in_execute_pct", events) == pytest.approx(22.5)
    assert read("idle_in_assemble_pct", events) == pytest.approx(17.5)
    assert read("sync_host_s", events) == pytest.approx(2.0)


def test_scope_unions_count_a_while_op_and_its_body_once():
    events = one_cut()
    assert read("round_device_s", events) == pytest.approx(10.0)   # not 15
    assert read("exact_eval_s", events) == pytest.approx(3.0)      # [5, 8]
    got = spans.reduce(events)["scopes"]
    assert got["ts_move_gen"] == pytest.approx(1.0)
    assert got["ts_commit"] is None
    # a scope is a whole component of the stack, not a prefix of one
    near = one_cut() + [op("fusion.9", 16, 1, "jit(one)/ts_exact_eval_x/add")]
    assert read("exact_eval_s", near) == pytest.approx(3.0)


def test_spans_open_when_the_session_started_or_stopped_are_restored():
    """The profiler drops a span that is open when it starts or stops; the
    cut's recorded spans bound it.  Cut 8 began executing before the
    stretch, cut 4's assembly too, and cut 12's assembly outlasts it."""
    events = [
        span("bench.traced", 0, 20, line="python"),
        op("while.1", 4, 10, LOOP),
        span("repro.engine.inits", 0, 1, line="serve-dispatch", cut=4, rid=6),
        span("repro.engine.pack", 1, 0.2, line="serve-dispatch", cut=4),
        span("repro.search.launch", 3.9, 0.1, cut=8),
        span("repro.search.readback", 4, 10, cut=8),
        span("repro.search.sync", 14, 1.8, cut=8),
        span("repro.search.finish", 15.8, 0.1, cut=8),
        span("repro.engine.fanout", 15.9, 0.05, cut=8, rid=8),
        span("repro.engine.fanout", 15.95, 0.05, cut=8, rid=9),
        span("repro.engine.inits", 16.5, 0.5, line="serve-dispatch", cut=12, rid=12),
        span("repro.engine.inits", 17, 1, line="serve-dispatch", cut=12, rid=13),
    ]
    # execute restored as [0, 16], its prep as [0, 3.9]
    assert read("search_prep_s", events) == pytest.approx(3.9)
    assert read("idle_in_execute_pct", events) == pytest.approx(100 * 6 / 20)
    # assembly: [0, 1.2] (under execute) and [16.5, 20]
    assert read("idle_in_assemble_pct", events) == pytest.approx(100 * 3.5 / 20)
    # spans with no cut (a solo search) are taken as recorded
    solo = [e for e in events if e.cut is None] + [
        dataclasses.replace(e, cut=None) for e in events if e.cut is not None]
    assert read("search_prep_s", solo) is None
    assert read("idle_in_execute_pct", solo) == pytest.approx(0.0)


def test_means_are_per_launch_and_clipped_to_the_stretch():
    events = [
        span("bench.traced", 0, 40, line="python"),
        span("repro.search.prep", -1, 3),       # clipped to [0, 2]
        span("repro.search.launch", 2, 0.1),
        op("while.1", 2.1, 10, LOOP),
        op("fusion.2", 3, 4, LOOP + "/body/ts_exact_eval/dot"),
        span("repro.search.sync", 12.1, 2),
        span("repro.search.finish", 14.1, 1),
        span("repro.search.prep", 16, 4),
        span("repro.search.launch", 20, 0.1),
        op("while.1", 20.1, 6, LOOP),
        op("fusion.2", 21, 2, LOOP + "/body/ts_exact_eval/dot"),
        span("repro.search.sync", 26.1, 1),
        span("repro.engine.fanout", 27.1, 1),
        span("repro.search.launch", 41, 0.1),   # outside: not counted
    ]
    assert read("search_prep_s", events) == pytest.approx((2 + 4) / 2)
    assert read("round_device_s", events) == pytest.approx((10 + 6) / 2)
    assert read("exact_eval_s", events) == pytest.approx((4 + 2) / 2)
    assert read("sync_host_s", events) == pytest.approx((2 + 1 + 1 + 1) / 2)


def test_nothing_to_read_gives_none():
    no_program = [span("bench.traced", 0, 20, line="python"),
                  op("while.1", 4, 10, "jit(one)/while"),
                  span("bench.engine.execute", 1, 15)]
    no_stretch = [e for e in one_cut() if e.name != "bench.traced"]
    for events in ([], no_program, no_stretch):
        assert spans.reduce(events) is None
        for name in READERS:
            assert read(name, events) is None
    # spans but no device plane: a CPU run, which measures nothing
    host_only = [e for e in one_cut() if e.plane == HOST]
    assert spans.reduce(host_only) is None
    for name in READERS:
        assert read(name, host_only) is None


def test_recorded_trace_of_a_program_without_spans(tmp_path):
    """The trace the JAX profiler wrote on the CPU for the benchmark's own
    spans: ``load`` keeps its stretch, and no reader finds anything."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(FIXTURES / "cpu_trace.xplane.pb", d / "run.xplane.pb")
    events = spans.load(str(tmp_path))
    assert [e.name for e in events] == ["bench.traced"]
    assert spans.load(str(tmp_path / "none")) == []
    for name in READERS:
        assert read(name, events) is None


def test_recorded_trace_with_a_device_plane(tmp_path):
    """A small XSpace laid out as a v5e trace is: a device plane with no op
    line sorted before the chip's, each op's name stack on its event
    metadata (as a string, and as a reference to a stat name), host spans
    with their ``cut`` and ``rid``.  The launch's ``prep`` and ``execute``
    began before the session and are restored."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(FIXTURES / "spans_trace.xplane.pb", d / "run.xplane.pb")
    events = spans.load(str(tmp_path))
    ops = [(e.plane, e.stack) for e in events if e.plane == DEV]
    assert ops == [(DEV, "jit(one)/vmap(ts_round)/while:"),
                   (DEV, "jit(one)/vmap(ts_round)/while/body/ts_exact_eval/"
                         "jit(take_along_axis)/gather:")]
    meta = {e.name: (e.cut, e.rid) for e in events if e.plane == HOST}
    assert meta["repro.search.readback"] == (7, None)
    assert meta["repro.engine.inits"] == (7, 8)
    assert read("round_device_s", events) == pytest.approx(4.0)
    assert read("exact_eval_s", events) == pytest.approx(1.0)
    assert read("search_prep_s", events) == pytest.approx(0.9)
    assert read("idle_in_execute_pct", events) == pytest.approx(60.0)


def test_readers_find_the_run_s_own_trace(tmp_path, monkeypatch):
    """A reader given the run as ``bench/run.py`` builds it (window, set-up,
    ``bench.trace``'s reduction) finds the trace the run wrote under
    ``.bench_cache/trace/<cell>``, reduces it once, and reads nothing from
    a trace whose stretch is not the run's, nor without a trace."""
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    d = tmp_path / "cell" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(FIXTURES / "spans_trace.xplane.pb", d / "run.xplane.pb")
    reduced = trace.reduce(trace.load(str(tmp_path / "cell")))

    def reading(name, run):
        return importlib.import_module(f"bench.metrics.{name}").read(run)

    run = types.SimpleNamespace(window=None, setup_s=0.0, trace=reduced)
    assert reading("round_device_s", run) == pytest.approx(4.0)
    assert reading("idle_in_execute_pct", run) == pytest.approx(60.0)
    assert run.spans == spans.reduce(spans.load(str(tmp_path / "cell")))
    other = types.SimpleNamespace(trace=dict(reduced, window_s=11.0))
    untraced = types.SimpleNamespace(trace=None)
    for name in READERS:
        assert reading(name, other) is None
        assert reading(name, untraced) is None
