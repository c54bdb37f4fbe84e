"""Mean over the window's answered requests of the served makespan over the
benchmark's own lower bound of the instance (``bench.bounds``)."""


def read(run):
    w = run.window
    return w.mk_over_lb() if w.answered else None
