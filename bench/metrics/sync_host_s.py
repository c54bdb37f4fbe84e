"""Search driver: host seconds after each launch's readback, per launch
(``repro.search.sync``, ``repro.search.finish`` and ``repro.engine.fanout``:
Algorithm 3 and the exact re-schedules at each sync, the repair of the
bests, the reports), over the traced stretch (``bench.spans``)."""
from bench import spans


def read(run):
    s = spans.of(run)
    if not s or not s["launches"]:
        return None
    return s["sync_host_s"] / s["launches"]
