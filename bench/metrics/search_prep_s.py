"""Search driver: mean seconds of the host's preparation before a cut's
first launch (``repro.search.prep``: Algorithm 3 and the exact schedule of
every walk start, packing), over the traced stretch (``bench.spans``).
Nothing to read without the program's spans."""
from bench import spans


def read(run):
    s = spans.of(run)
    if not s or not s["prep_s"]:
        return None
    return sum(s["prep_s"]) / len(s["prep_s"])
