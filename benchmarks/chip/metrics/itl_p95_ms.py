"""95th percentile of every gap between consecutive tokens of a request,
pooled over all requests, for gaps that end inside the window."""
import numpy as np


def read(run):
    lo, hi = run.window
    gaps = [b - a for r in run.requests for a, b in zip(r.token_times, r.token_times[1:])
            if lo <= b < hi]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
