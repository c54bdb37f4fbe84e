"""Exact-eval kernel: seconds per launch in which an op of the chunked
top-K exact evaluation (named scope ``ts_exact_eval``: the splice and the
schedule-DP sweep) runs on the device, over the traced stretch
(``bench.spans``; a union of intervals)."""
from bench import spans


def read(run):
    s = spans.of(run)
    if not s or not s["launches"] or s["scopes"]["ts_exact_eval"] is None:
        return None
    return s["scopes"]["ts_exact_eval"] / s["launches"]
