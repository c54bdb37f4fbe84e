"""Device: percent of the traced stretch in which no op runs on the device,
no cut executes, and the dispatch thread assembles the next cut
(``repro.engine.assemble`` open; ``bench.spans``)."""
from bench import spans


def read(run):
    s = spans.of(run)
    if not s:
        return None
    return 100.0 * s["idle_in_assemble_s"] / s["window_s"]
