"""Device: percent of the traced stretch in which no op runs on the device
while the engine executes a cut (``repro.engine.execute`` open: the host's
preparation, syncs and reports between launches; ``bench.spans``)."""
from bench import spans


def read(run):
    s = spans.of(run)
    if not s:
        return None
    return 100.0 * s["idle_in_execute_s"] / s["window_s"]
