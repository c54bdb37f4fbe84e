"""Nearest-rank 95th percentile of send-to-answer seconds, on the client's
clock, over every request answered in the window."""


def read(run):
    w = run.window
    return w.latency_p95_s() if w.answered else None
