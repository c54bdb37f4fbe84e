"""Reduction of a profiler trace to device busy time, idle share and the
run's breakdown.

:func:`load` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
only what the reduction needs, as plain :class:`Event` records: the
device operations of every accelerator plane, and the benchmark's own
host spans (TraceAnnotations whose names start with ``bench.``).
:func:`reduce` then works on those records alone, so a small recorded
trace checks it (``bench/tests``).

* The traced stretch is the ``bench.traced`` span.
* A device is busy while any of its operations runs: the union of the
  intervals of its ``XLA Ops`` line (its ``XLA Modules`` line where it has
  no op line), clipped to the stretch.  ``busy_s`` is that union's length
  averaged over the devices, ``window_s`` the stretch's length.
* ``device_ops``: the operations that took the most device time, summed
  by name over every device.
* ``idle_gaps``: the longest gaps in the first device's busy union, each
  named after the host span that covers most of it (``host idle`` where
  none does).
"""
from __future__ import annotations

import dataclasses
import glob
import os

__all__ = ["Event", "load", "reduce", "TOP"]

TOP = 10
STRETCH = "bench.traced"
OP_LINES = ("XLA Ops", "XLA Modules")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def load(log_dir: str) -> list:
    """The events of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        return []
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = _is_device(plane.name)
        for line in plane.lines:
            if device and line.name not in OP_LINES:
                continue
            for e in line.events:
                if device or e.name.startswith("bench."):
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns), float(e.duration_ns)))
    return events


def _union(intervals, lo: float, hi: float) -> list:
    """Merged, clipped ``[(start, end)]`` of ``intervals``."""
    merged = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _op_events(events) -> dict:
    """Per device plane, the events of its op line (or module line)."""
    by_plane: dict = {}
    for e in events:
        if _is_device(e.plane):
            by_plane.setdefault(e.plane, {}).setdefault(e.line, []).append(e)
    return {p: next(lines[n] for n in OP_LINES if n in lines)
            for p, lines in by_plane.items()}


def reduce(events) -> "dict | None":
    """Busy and window seconds, top operations and longest idle gaps, or
    None where the trace holds no stretch or no device operation."""
    stretch = [e for e in events if e.name == STRETCH]
    ops = _op_events(events)
    if not stretch or not ops:
        return None
    lo, hi = stretch[0].start_ns, stretch[0].end_ns
    unions = {p: _union([(e.start_ns, e.end_ns) for e in evs], lo, hi)
              for p, evs in sorted(ops.items())}
    busy = sum(sum(e - s for s, e in u) for u in unions.values()) / len(unions)
    if busy <= 0:
        return None

    per_op: dict = {}
    for evs in ops.values():
        for e in evs:
            overlap = min(e.end_ns, hi) - max(e.start_ns, lo)
            if overlap > 0:
                per_op[e.name] = per_op.get(e.name, 0.0) + overlap
    top_ops = sorted(per_op.items(), key=lambda x: -x[1])[:TOP]

    spans = [e for e in events if e.name.startswith("bench.") and e.name != STRETCH]
    first = unions[sorted(unions)[0]]
    edges = [lo] + [x for s, e in first for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    def label(s, e):
        cover = {}
        for sp in spans:
            c = min(e, sp.end_ns) - max(s, sp.start_ns)
            if c > 0:
                cover[sp.name] = cover.get(sp.name, 0.0) + c
        return max(cover, key=cover.get) if cover else "host idle"

    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[label(s, e), (e - s) / 1e9] for s, e in gaps[:TOP]]
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": [[n, t / 1e9] for n, t in top_ops],
            "idle_gaps": idle}
