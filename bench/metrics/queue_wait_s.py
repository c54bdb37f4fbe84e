"""Front end: mean seconds a window request waited in the service's queue
before its cut was taken (``RequestResult.metrics["queue_wait"]``)."""
import math


def read(run):
    vals = [c.queue_wait for c in run.window.answered
            if not math.isnan(c.queue_wait)]
    return sum(vals) / len(vals) if vals else None
