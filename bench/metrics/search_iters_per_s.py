"""Request-iterations of the search over the window's length: each answered
request counts its report's ``iterations`` (pad lanes are never answered)."""


def read(run):
    w = run.window
    return w.search_iters_per_s() if w.answered else None
