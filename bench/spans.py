"""Reduction of a profiler trace to the program's own spans and scopes.

:func:`load` reads the ``.xplane.pb`` that :mod:`bench.trace` reads and
keeps, as plain :class:`Event` records: the ``bench.traced`` stretch, the
program's host spans (TraceAnnotations whose names start with ``repro.``,
with the ``cut`` and ``rid`` they carry) and the device operations of the
first accelerator plane, each with its name stack (the op event's
``tf_op`` stat, e.g. ``jit(one)/vmap(ts_round)/while/body/ts_move_gen/lt:``;
a scope matches a whole word of it).
:func:`reduce` then works on those records alone, so hand-made events
check it (``bench/tests/test_bench_spans.py``).

The profiler records a span only where it begins inside the session, and
the benchmark opens the stretch when a cut's answers arrive, by which time
the engine has already begun to execute the next cut.  So :func:`reduce`
first restores, per cut, the clipped part of a span that was open when the
session started or stopped, from the spans of that cut recorded inside it:

* ``repro.engine.execute``: from the stretch's start where the cut's
  ``repro.search.prep`` is missing (else its first search span), to the
  stretch's end where its ``repro.search.finish`` is missing (else its last
  search or fan-out span);
* ``repro.search.prep``: from the stretch's start to the cut's first
  ``repro.search.launch``, where the launch was recorded and the prep not;
* ``repro.engine.assemble``: from the stretch's start where the ``inits``
  of the cut's head request is missing, to the stretch's end where its
  ``repro.engine.pack`` is.

Then everything is clipped to the stretch, and every length is the length
of a union: a ``while`` op encloses the ops of its body, and two spans of
one name may overlap.

* ``launches``: ``repro.search.launch`` spans in the stretch.
* ``prep_s``: the length of each ``repro.search.prep`` span.
* ``sync_host_s``: the union of ``repro.search.sync``,
  ``repro.search.finish`` and ``repro.engine.fanout``.
* ``scopes``: per device scope (``ts_round``, ``ts_exact_eval``, ...), the
  union of the ops whose name stack holds it, also inside a transform such
  as ``vmap(ts_round)``; None where no op does.
* ``idle_in_execute_s``: no device op runs and ``repro.engine.execute`` is
  open; ``idle_in_assemble_s``: no device op runs, no execute is open and
  ``repro.engine.assemble`` is.

:func:`reduce` gives None where the trace holds no stretch, no program span
(a program without spans) or no device op (a CPU run, which measures
nothing).

:func:`of` is what the readers call: it finds the trace a ``--trace 1`` run
wrote (``bench/run.py`` keeps it under ``.bench_cache/trace/<cell>``),
reduces it once and keeps the result on the run (``run.spans``).
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from pathlib import Path

from bench.trace import OP_LINES, STRETCH, _is_device, _union
from bench.trace import Event as _Event

__all__ = ["Event", "load", "reduce", "of", "SCOPES", "STACK_STAT"]

PREFIX = "repro."
TRACES = Path(__file__).resolve().parent.parent / ".bench_cache" / "trace"
STACK_STAT = "tf_op"
SCOPES = ("ts_round", "ts_move_gen", "ts_approx_eval", "ts_exact_eval",
          "ts_perturb", "ts_commit")
SYNC_HOST = ("repro.search.sync", "repro.search.finish",
             "repro.engine.fanout")
SEARCH = ("repro.search.prep", "repro.search.launch", "repro.search.readback",
          "repro.search.sync", "repro.search.finish", "repro.engine.fanout")


@dataclasses.dataclass(frozen=True)
class Event(_Event):
    stack: str = ""             # a device op's name stack
    cut: "int | None" = None    # a span's cut: its head request's id
    rid: "int | None" = None    # a span's own request, where it has one


def load(log_dir: str) -> list:
    """The stretch, the program spans and the first device's ops of the
    newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    devices = sorted(p.name for p in data.planes if _is_device(p.name)
                     and any(line.name in OP_LINES for line in p.lines))
    stacks = _name_stacks(paths[-1], devices[0]) if devices else {}
    events = []
    for plane in data.planes:
        if _is_device(plane.name):
            if plane.name not in devices[:1]:
                continue
            names = [line.name for line in plane.lines]
            name = next(n for n in OP_LINES if n in names)
            for line in plane.lines:
                if line.name != name:
                    continue
                for e in line.events:
                    events.append(Event(plane.name, name, e.name,
                                        float(e.start_ns), float(e.duration_ns),
                                        stacks.get(e.name, "")))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX) or e.name == STRETCH:
                    meta = dict(e.stats)
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns), float(e.duration_ns),
                                        cut=meta.get("cut"), rid=meta.get("rid")))
    return events


def _fields(buf):
    """``(field number, value)`` of each field of a protobuf message: an int
    for a varint, a memoryview for any other wire type."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _name_stacks(path: str, plane_name: str) -> dict:
    """The ``tf_op`` stat of every op of one plane, by the op's name.

    The stat sits on the op's event metadata, which ``ProfileData`` does
    not expose, so the plane is read from the file's XSpace message:
    planes (field 1); a plane's name (2), event metadata (4: id -> name 2,
    stats 5) and stat metadata (5: id -> name 2); a stat's metadata id (1)
    and its string (5), or a reference (7) to a stat metadata's name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, plane in _fields(space):
        if number != 1:
            continue
        fields = _fields(plane)
        if next((_text(v) for n, v in fields if n == 2), None) != plane_name:
            continue
        ops, stat_names = [], {}
        for n, entry in fields:
            if n not in (4, 5):
                continue
            value = dict(_fields(entry)).get(2, b"")
            if n == 5:
                meta = dict(_fields(value))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
                continue
            name, stats = "", []
            for k, v in _fields(value):
                if k == 2:
                    name = _text(v)
                elif k == 5:
                    stats.append(dict(_fields(v)))
            ops.append((name, stats))
        tf_op = next((i for i, s in stat_names.items() if s == STACK_STAT), None)
        out = {}
        for name, stats in ops:
            for st in stats:
                if st.get(1) == tf_op:
                    out.setdefault(name, _text(st[5]) if 5 in st
                                   else stat_names.get(st.get(7), ""))
        return out
    return {}


def _restored(spans, lo: float, hi: float) -> list:
    """``spans`` and, per cut, the spans that were open when the session
    started or stopped, as far as the cut's recorded spans bound them."""
    out = list(spans)
    by_cut: dict = {}
    for e in spans:
        if e.cut is not None:
            by_cut.setdefault(e.cut, []).append(e)

    def add(like, name, start, end):
        if end > start:
            out.append(dataclasses.replace(like, name=name, start_ns=start,
                                           dur_ns=end - start, rid=None))

    for cut, evs in by_cut.items():
        names = {e.name for e in evs}
        search = [e for e in evs if e.name in SEARCH]
        if search and "repro.engine.execute" not in names:
            add(search[0], "repro.engine.execute",
                min(e.start_ns for e in search)
                if "repro.search.prep" in names else lo,
                max(e.end_ns for e in search)
                if "repro.search.finish" in names else hi)
        launches = [e for e in evs if e.name == "repro.search.launch"]
        if launches and "repro.search.prep" not in names:
            add(launches[0], "repro.search.prep", lo,
                min(e.start_ns for e in launches))
        parts = [e for e in evs
                 if e.name in ("repro.engine.inits", "repro.engine.pack")]
        if parts and "repro.engine.assemble" not in names:
            head = any(e.name == "repro.engine.inits" and e.rid == cut
                       for e in parts)
            add(parts[0], "repro.engine.assemble",
                min(e.start_ns for e in parts) if head else lo,
                max(e.end_ns for e in parts)
                if "repro.engine.pack" in names else hi)
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a, b) -> list:
    """The parts of the merged intervals ``a`` outside the merged ``b``."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append([s, bs])
            s = max(s, be)
        if e > s:
            out.append([s, e])
    return out


def _meet(a, b) -> list:
    """The parts of the merged intervals ``a`` inside the merged ``b``."""
    return [[max(s, bs), min(e, be)] for s, e in a for bs, be in b
            if min(e, be) > max(s, bs)]


def reduce(events) -> "dict | None":
    stretch = [e for e in events if e.name == STRETCH]
    spans = [e for e in events if e.name.startswith(PREFIX)]
    ops = [e for e in events if _is_device(e.plane)]
    if not stretch or not spans or not ops:
        return None
    lo, hi = stretch[0].start_ns, stretch[0].end_ns
    spans = _restored(spans, lo, hi)

    def union(evs):
        return _union([(e.start_ns, e.end_ns) for e in evs], lo, hi)

    def named(*names):
        return [e for e in spans if e.name in names and union([e])]

    scopes = {}
    for scope in SCOPES:
        under = [e for e in ops if scope in re.split(r"[/():]", e.stack)]
        scopes[scope] = _length(union(under)) / 1e9 if under else None
    idle = _minus([[lo, hi]], union(ops))
    execute = union(named("repro.engine.execute"))
    assemble = union(named("repro.engine.assemble"))
    return {
        "window_s": (hi - lo) / 1e9,
        "launches": len(named("repro.search.launch")),
        "prep_s": [_length(union([e])) / 1e9 for e in named("repro.search.prep")],
        "sync_host_s": _length(union(named(*SYNC_HOST))) / 1e9,
        "scopes": scopes,
        "idle_in_execute_s": _length(_meet(idle, execute)) / 1e9,
        "idle_in_assemble_s": _length(_meet(_minus(idle, execute),
                                             assemble)) / 1e9,
    }


def of(run) -> "dict | None":
    """:func:`reduce` of the trace ``run`` wrote, kept as ``run.spans``.

    The trace is the newest ``.xplane.pb`` under :data:`TRACES`, and is
    taken only where its stretch has the length that :mod:`bench.trace`
    read from it (``run.trace["window_s"]``): a trace left by an earlier
    run is never read for this one.  None where the run has no trace."""
    if not hasattr(run, "spans"):
        run.spans = None
        paths = glob.glob(str(TRACES / "*" / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        if run.trace and paths:
            newest = Path(max(paths, key=os.path.getmtime))
            s = reduce(load(str(newest.parents[3])))
            if s and math.isclose(s["window_s"], run.trace["window_s"],
                                  rel_tol=1e-9):
                run.spans = s
    return run.spans
