"""Device: percent of the traced stretch in which no operation ran on the
device (``bench.trace``).  Nothing to read without a trace."""


def read(run):
    t = run.trace
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
