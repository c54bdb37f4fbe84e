"""Device program: seconds per launch in which an op of the round loop
(named scope ``ts_round``) runs on the device, over the traced stretch
(``bench.spans``; a union, since the loop's ``while`` op encloses its
body's ops)."""
from bench import spans


def read(run):
    s = spans.of(run)
    if not s or not s["launches"] or s["scopes"]["ts_round"] is None:
        return None
    return s["scopes"]["ts_round"] / s["launches"]
