"""Median, over every request due in the window, of due time to
first token; a request with no first token by the window's close counts
at the time it had waited by then."""
import numpy as np


def read(run):
    lo, hi = run.window
    waits = [min(r.token_times[0] if r.token_times else hi, hi) - r.due
             for r in run.requests if lo <= r.due < hi]
    return float(np.percentile(waits, 50)) * 1e3 if waits else None
