"""The reduction from trace events to busy time, idle share and breakdown."""
import shutil
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event

FIXTURES = Path(__file__).resolve().parent / "fixtures"
DEV = "/device:TPU:0"
S = 1e9  # ns per second


def ev(name, start, dur, plane=DEV, line="XLA Ops"):
    return Event(plane, line, name, start * S, dur * S)


def test_busy_is_the_clipped_union_of_device_ops():
    events = [
        ev("bench.traced", 10, 10, plane="/host:CPU", line="main"),
        ev("fusion.1", 8, 4),           # clipped to [10, 12]
        ev("fusion.2", 11, 1.5),        # overlaps: union [10, 12.5]
        ev("while.3", 15, 2.5),         # [15, 17.5]
        ev("copy.4", 19, 5),            # clipped to [19, 20]
        ev("bench.engine.execute", 13, 2, plane="/host:CPU", line="serve-solve"),
        ev("bench.engine.assemble", 17, 1.5, plane="/host:CPU", line="dispatch"),
    ]
    got = trace.reduce(events)
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx(2.5 + 2.5 + 1)
    names = [n for n, _ in got["device_ops"]]
    assert names == ["while.3", "fusion.1", "fusion.2", "copy.4"]
    assert got["device_ops"][1][1] == pytest.approx(2.0)
    gaps = got["idle_gaps"]
    assert [round(s, 6) for _, s in gaps] == [2.5, 1.5]
    assert [label for label, _ in gaps] == ["bench.engine.execute",
                                            "bench.engine.assemble"]


def test_modules_line_stands_in_for_a_missing_op_line():
    events = [ev("bench.traced", 0, 4, plane="/host:CPU", line="main"),
              ev("jit_one", 1, 1, line="XLA Modules")]
    got = trace.reduce(events)
    assert got["busy_s"] == pytest.approx(1.0)
    assert got["idle_gaps"][0] == ["host idle", pytest.approx(2.0)]


def test_busy_is_averaged_over_devices():
    events = [ev("bench.traced", 0, 10, plane="/host:CPU", line="main"),
              ev("op", 0, 4), ev("op", 0, 6, plane="/device:TPU:1")]
    assert trace.reduce(events)["busy_s"] == pytest.approx(5.0)


def test_nothing_to_read_gives_none():
    assert trace.reduce([]) is None
    assert trace.reduce([ev("op", 0, 1)]) is None            # no stretch
    host_only = [ev("bench.traced", 0, 1, plane="/host:CPU", line="main"),
                 ev("dot", 0, 1, plane="/host:CPU", line="tf_XLA")]
    assert trace.reduce(host_only) is None                   # no device plane


def recorded(tmp_path, name):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(FIXTURES / name, d / "run.xplane.pb")
    return trace.load(str(tmp_path))


def test_recorded_cpu_trace(tmp_path):
    """A trace the JAX profiler wrote on the CPU, with the benchmark's host
    spans in it: ``load`` keeps those spans and nothing of the host's own
    work, and with no device plane there is nothing to reduce."""
    events = recorded(tmp_path, "cpu_trace.xplane.pb")
    by_name = {e.name: e for e in events}
    assert set(by_name) == {"bench.traced", "bench.submit", "bench.engine.execute"}
    outer = by_name["bench.traced"]
    for e in events:
        assert e.dur_ns > 0 and outer.start_ns <= e.start_ns <= e.end_ns <= outer.end_ns
    assert trace.reduce(events) is None
    # device ops on the recorded clock: the gap under a recorded span takes
    # that span's name
    sub = by_name["bench.submit"]
    ops = [Event(DEV, "XLA Ops", "fusion", outer.start_ns, sub.start_ns - outer.start_ns),
           Event(DEV, "XLA Ops", "fusion", sub.end_ns, outer.end_ns - sub.end_ns)]
    got = trace.reduce(events + ops)
    assert got["window_s"] == pytest.approx(outer.dur_ns / S)
    assert got["busy_s"] == pytest.approx((outer.dur_ns - sub.dur_ns) / S)
    assert got["idle_gaps"] == [["bench.submit", pytest.approx(sub.dur_ns / S)]]
