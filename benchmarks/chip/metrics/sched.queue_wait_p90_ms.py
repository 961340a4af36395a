"""90th percentile, over requests due in the window, of due time to the
start of the scheduler's admission (the ``admit`` span, or the first
``chunk`` span of a chunked admission), from the program's tracer; a
request not admitted by the close counts at its wait by then."""
import numpy as np


def read(run):
    if not run.spans:
        return None
    start = {}
    for kind, t, _, args in run.spans:
        if kind in ("admit", "chunk"):
            start[args["req"]] = min(start.get(args["req"], t), t)
    lo, hi = run.window
    waits = [min(start.get(r.index, hi), hi) - r.due
             for r in run.requests if lo <= r.due < hi]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
