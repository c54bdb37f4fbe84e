"""Algorithm 3 (host): seconds per launch in which the search thread runs
Algorithm 3 (``repro.search.alg3`` spans, in the cut's ``prep``, ``sync``
and ``finish``), over the traced stretch: a union of the spans recorded
in it, clipped to it.  The profiler records no span that began before its
session, so an Algorithm 3 call already running when the stretch opens is
not counted.  Nothing to read without the program's spans or a device."""
import glob
import os
from pathlib import Path

from bench import spans, trace

SPAN = "repro.search.alg3"


def per_launch(events) -> "float | None":
    """The metric from a trace's events (``bench.spans.load``)."""
    reduced = spans.reduce(events)
    alg3 = [(e.start_ns, e.end_ns) for e in events if e.name == SPAN]
    if not reduced or not reduced["launches"] or not alg3:
        return None
    stretch = next(e for e in events if e.name == trace.STRETCH)
    covered = trace._union(alg3, stretch.start_ns, stretch.end_ns)
    return sum(hi - lo for lo, hi in covered) / 1e9 / reduced["launches"]


def read(run):
    if not spans.of(run):   # no trace of this run
        return None
    paths = glob.glob(str(spans.TRACES / "*" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    newest = Path(max(paths, key=os.path.getmtime))
    return per_launch(spans.load(str(newest.parents[3])))
