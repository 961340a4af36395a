"""Output tokens delivered inside the window, over the window's seconds."""


def read(run):
    lo, hi = run.window
    n = sum(1 for r in run.requests for t in r.token_times if lo <= t < hi)
    return n / run.seconds
