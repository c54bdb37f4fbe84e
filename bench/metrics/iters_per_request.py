"""Search driver: mean search iterations (``report.iterations``) of the
window's answered requests."""


def read(run):
    a = run.window.answered
    return sum(c.iterations for c in a) / len(a) if a else None
